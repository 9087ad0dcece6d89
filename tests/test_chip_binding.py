"""One process per chip: what the scheduler does with `num_tpus`, checked on
the CPU with RAY_TPU_NUM_CHIPS=2 standing in for two device nodes. Each test
drives a fresh driver process (the suite's own session has no chips)."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _drive(body: str, **env_extra) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    for k in ("RAY_TPU_ARENA", "RAY_TPU_ADDRESS", "RAY_TPU_NUM_CHIPS",
              "JAX_COMPILATION_CACHE_DIR"):
        env.pop(k, None)
    env.update(env_extra)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)], env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, f"--- stdout\n{r.stdout}\n--- stderr\n{r.stderr[-6000:]}"
    return r.stdout


def test_actors_and_tasks_bind_to_disjoint_chips():
    out = _drive("""
        import os, sys
        import ray_tpu

        ray_tpu.init(num_cpus=4)
        assert ray_tpu.cluster_resources()["TPU"] == 2.0

        def seen():
            keys = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
                    "TPU_PROCESS_BOUNDS", "JAX_PLATFORMS",
                    "JAX_COMPILATION_CACHE_DIR")
            return ({k: os.environ.get(k) for k in keys},
                    ray_tpu.get_tpu_ids(), os.getpid())

        @ray_tpu.remote(num_tpus=1)
        class Bound:
            def seen(self):
                return seen()

        @ray_tpu.remote(num_tpus=1)
        def bound_task():
            return seen()

        @ray_tpu.remote
        def plain_task():
            return seen()

        a = Bound.remote()
        env_a, ids_a, pid_a = ray_tpu.get(a.seen.remote(), timeout=60)
        env_t, ids_t, pid_t = ray_tpu.get(bound_task.remote(), timeout=60)
        # disjoint chips, each worker bound through the env libtpu reads
        assert ids_a == [0] and ids_t == [1], (ids_a, ids_t)
        assert env_a["TPU_VISIBLE_CHIPS"] == "0" and env_t["TPU_VISIBLE_CHIPS"] == "1"
        for env in (env_a, env_t):   # a sub-host worker carries its bounds
            assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1", env
            assert env["TPU_PROCESS_BOUNDS"] == "1,1,1", env
            assert env["JAX_COMPILATION_CACHE_DIR"].endswith(".jax_cache"), env
        # the task's worker was its own and is gone: its chip is free again,
        # and a second task gets a fresh process, not a pooled one
        assert ray_tpu.available_resources()["TPU"] == 1.0
        _, ids_t2, pid_t2 = ray_tpu.get(bound_task.remote(), timeout=60)
        assert ids_t2 == [1] and pid_t2 != pid_t
        try:
            os.kill(pid_t, 0)
            raise AssertionError("chip-bound task worker outlived its task")
        except ProcessLookupError:
            pass
        # a worker nobody bound to a chip is kept off every chip
        env_p, ids_p, _ = ray_tpu.get(plain_task.remote(), timeout=60)
        assert ids_p == [] and env_p["TPU_VISIBLE_CHIPS"] is None
        assert env_p["JAX_PLATFORMS"] == "cpu"

        b = Bound.remote()
        assert ray_tpu.get(b.seen.remote(), timeout=60)[1] == [1]
        third = Bound.remote()                       # no chip left: pends
        ref = third.seen.remote()
        ready, _ = ray_tpu.wait([ref], timeout=2)
        assert not ready
        ray_tpu.kill(a)                              # a kill returns the chip
        _, ids_3, pid_3 = ray_tpu.get(ref, timeout=60)
        assert ids_3 == [0] and pid_3 != pid_a
        try:                                         # ...and the holder is gone
            os.kill(pid_a, 0)
            raise AssertionError("killed chip-bound actor still alive")
        except ProcessLookupError:
            pass
        assert "jax" not in sys.modules
        ray_tpu.shutdown()
        print("BINDING_OK")
        """, RAY_TPU_NUM_CHIPS="2")
    assert "BINDING_OK" in out


def test_whole_host_worker_needs_no_process_bounds():
    out = _drive("""
        import os
        import ray_tpu
        ray_tpu.init(num_cpus=2)

        @ray_tpu.remote(num_tpus=2)
        def both():
            return (os.environ.get("TPU_VISIBLE_CHIPS"),
                    os.environ.get("TPU_CHIPS_PER_PROCESS_BOUNDS"),
                    ray_tpu.get_tpu_ids())

        assert ray_tpu.get(both.remote(), timeout=60) == ("0,1", None, [0, 1])
        ray_tpu.shutdown()
        print("WHOLE_HOST_OK")
        """, RAY_TPU_NUM_CHIPS="2")
    assert "WHOLE_HOST_OK" in out


def test_init_counts_chips_without_importing_jax():
    """No override: chips come from the device nodes (none on this box), and
    the driver still has not imported jax — on libtpu that import would take
    the chip from the workers."""
    out = _drive("""
        import sys
        import ray_tpu
        from ray_tpu.util.tpu import count_local_chips
        ray_tpu.init(num_cpus=2)
        assert "jax" not in sys.modules
        assert ray_tpu.cluster_resources().get("TPU", 0) == count_local_chips()
        ray_tpu.shutdown()
        print("NO_JAX_OK")
        """)
    assert "NO_JAX_OK" in out


def test_a_driver_reading_device_values_stays_off_jax():
    """A worker returns (and a train loop reports) device arrays; what the
    driver unpickles is numpy — jax's own pickling would device_put inside
    loads and open a backend in the driver."""
    out = _drive("""
        import sys
        import numpy as np
        import ray_tpu
        ray_tpu.init(num_cpus=2)

        @ray_tpu.remote
        def f():
            import jax.numpy as jnp
            return {"loss": jnp.float32(1.5),
                    "w": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3),
                    "big": jnp.ones((256, 256))}

        out = ray_tpu.get(f.remote(), timeout=120)
        assert "jax" not in sys.modules
        assert all(type(v) is np.ndarray for v in out.values()), out
        assert float(out["loss"]) == 1.5 and str(out["w"].dtype) == "bfloat16"
        assert out["big"].sum() == 65536.0
        ray_tpu.shutdown()
        print("HOST_VALUES_OK")
        """, RAY_TPU_NUM_CHIPS="0")
    assert "HOST_VALUES_OK" in out
