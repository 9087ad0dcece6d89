"""TPU host helpers (reference: python/ray/_private/accelerators/tpu.py).

Everything here runs WITHOUT importing jax: a libtpu chip belongs to the one
process that opened it, so the driver, the controller and CPU workers must be
able to count chips, bind a child to some of them and place the compile cache
without initialising a backend. Slice topology still comes from the TPU
runtime env vars (the GKE/GCE metadata conventions) so CPU tests can exercise
it via env injection; the peaks table is keyed by `device_kind` as the
chip-holding process reports it.
"""

import os
import re
from typing import Dict, List, Optional, Tuple

# What the scheduler sets to bind one worker process to a subset of this
# host's chips (the reference's TPU accelerator manager sets the same three
# for sub-host workers). libtpu reads them when the process opens the chip.
ACCEL_ENV_KEYS = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
                  "TPU_PROCESS_BOUNDS")

# chips per sub-host process -> TPU_CHIPS_PER_PROCESS_BOUNDS. 1 and 2 were
# run on a 2x2 v5e host (PERF.md, PR 21); 4 (half of a 2x4 host) is the
# reference accelerator manager's value, not run here
_PROCESS_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}


def scrub_accel_env(env: dict, n_cpu_devices: Optional[int] = None) -> dict:
    """Return a copy of `env` bound to CPU-only jax: no chip binding,
    JAX_PLATFORMS=cpu, optionally a virtual CPU device count."""
    env = dict(env)
    for k in ACCEL_ENV_KEYS:
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    if n_cpu_devices is not None:
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={n_cpu_devices}")
        env["XLA_FLAGS"] = " ".join(flags)
    return env


def count_local_chips() -> int:
    """How many TPU chips this host has, from its device nodes — `/dev/accelN`
    or the numeric entries of `/dev/vfio/` (how the reference's accelerator
    manager counts them). `RAY_TPU_NUM_CHIPS` overrides (tests, hosts whose
    chips must stay unscheduled). Opens nothing, imports no jax. Chips are
    addressed by their 0-based position in that enumeration, which is what
    libtpu's TPU_VISIBLE_CHIPS indexes — not by the node's own number."""
    env = os.environ.get("RAY_TPU_NUM_CHIPS")
    if env is not None:
        return int(env)
    for d, pat in (("/dev", r"accel\d+$"), ("/dev/vfio", r"\d+$")):
        try:
            n = sum(1 for name in os.listdir(d) if re.match(pat, name))
        except OSError:
            continue
        if n:
            return n
    return 0


def chip_binding_env(chip_ids: List[int], host_chips: int) -> Dict[str, str]:
    """Env that makes libtpu open exactly `chip_ids` in the spawned process.
    A worker that takes the whole host needs nothing beyond visibility; a
    sub-host worker also needs its process bounds, or the second such
    process on the host fails on libtpu's multi-process lockfile."""
    env = {"TPU_VISIBLE_CHIPS": ",".join(map(str, chip_ids)),
           "RAY_TPU_IDS": ",".join(map(str, chip_ids))}
    n = len(chip_ids)
    if n < host_chips:
        bounds = _PROCESS_BOUNDS.get(n)
        if bounds is None:
            raise ValueError(
                f"cannot give one process {n} of this host's {host_chips} "
                f"chips: libtpu forms sub-host processes of "
                f"{sorted(_PROCESS_BOUNDS)} chips only")
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return env


def compile_cache_dir() -> str:
    """Where every compiling process keeps JAX's persistent cache: the
    directory `JAX_COMPILATION_CACHE_DIR` names, else ONE fixed path inside
    the checkout. The path is part of the cache key's lookup, so it is never
    built from a pid, a timestamp or a temp dir."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


# generation → (chips per host, cores per chip)
_GEN_INFO = {
    "v2": (4, 2), "v3": (4, 2), "v4": (4, 2),
    "v5e": (8, 1), "v5litepod": (8, 1), "v5p": (4, 2), "v6e": (8, 1),
}


def get_tpu_generation() -> Optional[str]:
    acc = os.environ.get("TPU_ACCELERATOR_TYPE")
    if not acc:
        return None
    return acc.split("-")[0].lower()


def get_accelerator_type() -> Optional[str]:
    """Full slice name, e.g. "v5e-8" / "v5p-64"."""
    return os.environ.get("TPU_ACCELERATOR_TYPE")


def get_tpu_pod_name() -> Optional[str]:
    """The slice/pod this host belongs to (reference: TPU_NAME /
    CLOUD_TPU_TASK_ID conventions)."""
    return (os.environ.get("TPU_NAME")
            or os.environ.get("TPU_POD_NAME")
            or os.environ.get("HOSTNAME"))


def get_num_chips_in_slice() -> int:
    acc = get_accelerator_type()
    if acc and "-" in acc:
        try:
            n = int(acc.split("-")[-1])
            gen = acc.split("-")[0].lower()
            cores = _GEN_INFO.get(gen, (4, 1))[1]
            # accelerator_type counts CORES for v2-v4 ("v4-8" = 4 chips) and
            # CHIPS for v5e ("v5e-8" = 8 chips)
            return n // cores if cores > 1 else n
        except ValueError:
            pass
    return count_local_chips()


def get_chips_per_host() -> int:
    """From what the host itself shows, in order: its device nodes (a
    container may be handed fewer chips than the runtime's host bounds
    name), the runtime's host bounds, the slice generation's usual host.
    0 = nothing says this host has chips."""
    local = count_local_chips()
    if local:
        return local
    bounds = os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS")
    if bounds:
        n = 1
        for x in bounds.split(","):
            n *= int(x)
        return n
    gen = get_tpu_generation()
    return _GEN_INFO[gen][0] if gen in _GEN_INFO else 0


def get_num_hosts_in_slice() -> int:
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES")
    if hosts:
        return len(hosts.split(","))
    chips = get_num_chips_in_slice()
    per = get_chips_per_host()
    return max(-(-chips // per), 1) if chips and per else 1


def get_worker_id() -> int:
    return int(os.environ.get("TPU_WORKER_ID", 0))


def visible_chip_ids() -> List[int]:
    """Chips bound to this process (set by the scheduler's chip binding);
    an unbound process sees every chip of the host."""
    env = os.environ.get("TPU_VISIBLE_CHIPS") or os.environ.get("RAY_TPU_IDS")
    if env:
        return [int(x) for x in env.split(",") if x != ""]
    return list(range(count_local_chips()))


def slice_topology() -> Dict:
    """One-stop topology summary for schedulers/trainers."""
    return {
        "generation": get_tpu_generation(),
        "accelerator_type": get_accelerator_type(),
        "pod_name": get_tpu_pod_name(),
        "num_chips": get_num_chips_in_slice(),
        "num_hosts": get_num_hosts_in_slice(),
        "chips_per_host": get_chips_per_host(),
        "worker_id": get_worker_id(),
    }


def mesh_shape_for_slice(tp: int = 1) -> Tuple[int, int]:
    """(dp_like, tp) factorization of this slice's chips — the default mesh
    recipe when the user doesn't pick one."""
    chips = max(get_num_chips_in_slice(), 1)
    if chips % tp:
        raise ValueError(f"tp={tp} does not divide {chips} chips")
    return chips // tp, tp


# `jax.devices()[0].device_kind` → published per-chip peaks: dense bf16
# FLOP/s and HBM bytes/s. Source: Google Cloud TPU documentation, the "TPU
# v4" / "TPU v5e" / "TPU v5p" / "TPU v6e" system-architecture pages (v5e:
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s); jax's own
# pallas/mosaic/tpu_info.py carries the same figures. Used for MFU and
# roofline accounting, never for scheduling.
_PEAKS = {
    "TPU v4": {"bf16_flops": 275e12, "hbm_bytes_per_s": 1228e9},
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5": {"bf16_flops": 459e12, "hbm_bytes_per_s": 2765e9},
    "TPU v5p": {"bf16_flops": 459e12, "hbm_bytes_per_s": 2765e9},
    "TPU v6 lite": {"bf16_flops": 918e12, "hbm_bytes_per_s": 1640e9},
    "TPU v6e": {"bf16_flops": 918e12, "hbm_bytes_per_s": 1640e9},
}


def chip_peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of `device_kind` (as the process holding the chip
    read it from `jax.devices()[0].device_kind`). A kind that is not in the
    table is an error: a utilization over a guessed peak is a wrong number."""
    try:
        return _PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; add it to "
            f"ray_tpu.util.tpu._PEAKS with its source "
            f"(known: {sorted(_PEAKS)})") from None
