"""Test env: virtual 8-device CPU mesh, no TPU dependency (SURVEY.md §4)."""

import os

# Must be set before jax is imported anywhere in the test process: the suite
# runs on the CPU whatever the host has (the chip is checked by chip_smoke.py).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("RAY_TPU_NUM_CHIPS", "0")
# workers inherit this env, so jax-in-worker also sees the cpu mesh

import pytest


def pytest_report_header(config):
    """One visible line per native control-plane target: built or fallback
    (tools/build_native.sh is the standalone spelling of the same check).
    Tests exercise both paths — native when available, the pure-Python
    fallbacks always — so a toolchain-less box still runs green, it just
    says so here instead of silently testing half the matrix."""
    from ray_tpu._native import build_report
    return "native control plane: " + build_report()


@pytest.fixture(scope="session")
def ray_session():
    import ray_tpu
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(autouse=True)
def _reset_observability():
    """Isolate tracing state between tests: a test that flips RAY_TPU_TRACE*
    or fills the span ring must not leak into the next one. The metrics
    registry is intentionally NOT cleared here — session-scoped components
    (controller, dashboard) hold live Metric objects across tests and
    clear_registry() would orphan them; tests that need a clean registry
    call clear_registry() themselves."""
    yield
    from ray_tpu.util import tracing
    tracing.clear()
    tracing.refresh()
