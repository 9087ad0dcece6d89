"""Data-plane benchmark: parallel chunked transfer + batched get + pipeline
locality (PR 7 tentpole).

Measures the object transfer data plane against a real two-host cluster
(this process is the head; a worker-node agent subprocess is its own
controller + shm arena):

  * large-object pull MB/s, single-stream (RAY_TPU_TRANSFER_STREAMS=1 — the
    legacy RPC-staged path) vs N parallel range streams landing recv_into a
    preallocated shm slab (zero-copy)
  * batched get: `get(list_of_refs)` over many small node-held objects —
    one pull_objects RPC per owner node — vs the same refs pulled one get()
    at a time
  * streaming-pipeline locality: a map pipeline whose map tasks are tagged
    with their input block's owner (soft NodeAffinity locality hint);
    records the scheduler's locality hit rate and the cross-node block
    bytes actually moved (≈ 0 for a shuffle-free pipeline)

Both transfer modes run in ONE process: the stream count is read from the
environment at fetch time, so the baseline is the same build with the knob
turned down — the comparison isolates the data plane, not a code-version
diff. `speedup` is the parallel/single ratio of median MB/s.

Modes:
  --measure   real measurement child (run by bench.run_measure_child)
  --smoke     fast CPU correctness check: parallel fetch integrity, batched
              get ordering/dedup, pipeline locality hit rate ≥ 90% with
              ~zero cross-node block bytes (tier-1 test hook)
  (no flag)   parent: runs --measure once under a timeout and persists its
              record under benchmarks/results/

Never imports jax — the data plane is accelerator-agnostic; what it reports
are host counts and rates, never device metrics.
"""

import json
import os
import signal
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# a host-only bench: head and loopback nodes advertise no chips
os.environ.setdefault("RAY_TPU_NUM_CHIPS", "0")

SIZE_MB = int(os.environ.get("RAY_TPU_TRANSFER_BENCH_MB", 64))
REPS = int(os.environ.get("RAY_TPU_TRANSFER_BENCH_REPS", 3))
SMALL_N = int(os.environ.get("RAY_TPU_TRANSFER_BENCH_SMALL_N", 64))
PIPE_BLOCKS = int(os.environ.get("RAY_TPU_TRANSFER_BENCH_BLOCKS", 8))


def _p50(vals):
    s = sorted(vals)
    return s[len(s) // 2]


def _wait_for(pred, timeout, msg):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.2)
    raise TimeoutError("timed out waiting for " + msg)


class _Cluster:
    """Head in-process + one worker-node agent subprocess."""

    def __init__(self, head_cpus=2, node_cpus=4):
        import ray_tpu
        self.ray = ray_tpu
        ray_tpu.init(num_cpus=head_cpus, cluster_port=0)
        addr = ray_tpu.cluster_address()
        env = dict(os.environ)
        env.pop("RAY_TPU_ARENA", None)  # the node is its own session
        env.pop("RAY_TPU_ADDRESS", None)
        self.node = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu._private.node_main",
             "--address", addr, "--num-cpus", str(node_cpus),
             "--resources", '{"worker_node": 1}'],
            env=env, stdin=subprocess.DEVNULL, start_new_session=True)
        _wait_for(lambda: len(ray_tpu.nodes()) == 2, 60, "node registration")

    def node_rows(self):
        return self.ray.nodes()

    def close(self):
        if self.node.poll() is None:
            os.killpg(self.node.pid, signal.SIGKILL)
            self.node.wait(timeout=10)
        self.ray.shutdown()


def _transfer_section(cl, size_mb, reps):
    """Median MB/s pulling a node-held blob to the driver, single-stream
    (legacy RPC staging) vs parallel range streams."""
    import numpy as np
    ray = cl.ray
    n = size_mb * (1 << 20) // 8

    @ray.remote(resources={"worker_node": 0.1})
    def produce():
        return np.arange(n, dtype=np.float64)

    def timed_pull():
        ref = produce.remote()
        # registered on the node (remote location) but NOT yet pulled
        _wait_for(lambda: ray.wait([ref], num_returns=1, timeout=0.1)[0],
                  120, "remote result ready")
        t0 = time.perf_counter()
        out = ray.get(ref, timeout=180)
        dt = time.perf_counter() - t0
        assert out.shape == (n,) and float(out[n // 3]) == float(n // 3)
        del out, ref  # decref: free head + node copies before the next rep
        return (size_mb) / dt

    out = {}
    for label, streams in (("single", 1), ("parallel", 0)):
        if streams:
            os.environ["RAY_TPU_TRANSFER_STREAMS"] = str(streams)
        else:
            os.environ.pop("RAY_TPU_TRANSFER_STREAMS", None)  # default (4)
        from ray_tpu._private.node_agent import transfer_streams
        rates = [timed_pull() for _ in range(reps)]
        out[label] = {"mbps_p50": round(_p50(rates), 1),
                      "streams": transfer_streams()}
    out["speedup"] = round(
        out["parallel"]["mbps_p50"] / max(out["single"]["mbps_p50"], 1e-9), 2)
    return out


def _batched_get_section(cl, small_n, reps):
    """p50 seconds for one batched get of `small_n` node-held small objects
    (one pull_objects RPC per owner) vs the same refs pulled one at a time."""
    import numpy as np
    ray = cl.ray

    @ray.remote(num_returns=small_n, resources={"worker_node": 0.1})
    def produce_many():
        return tuple(np.full(1024, i, dtype=np.int64) for i in range(small_n))

    def fresh_refs():
        refs = produce_many.remote()
        _wait_for(lambda: len(ray.wait(refs, num_returns=small_n,
                                       timeout=0.1)[0]) == small_n,
                  120, "small objects ready")
        return refs

    batched, sequential = [], []
    for _ in range(reps):
        refs = fresh_refs()
        t0 = time.perf_counter()
        vals = ray.get(list(refs), timeout=120)
        batched.append(time.perf_counter() - t0)
        assert all(int(v[0]) == i for i, v in enumerate(vals))
        del vals, refs

        refs = fresh_refs()
        t0 = time.perf_counter()
        vals = [ray.get(r, timeout=120) for r in refs]
        sequential.append(time.perf_counter() - t0)
        assert all(int(v[0]) == i for i, v in enumerate(vals))
        del vals, refs
    return {"n": small_n,
            "batched_s_p50": round(_p50(batched), 4),
            "sequential_s_p50": round(_p50(sequential), 4),
            "speedup": round(_p50(sequential) / max(_p50(batched), 1e-9), 2)}


def _pipe_block(lo, hi):
    import numpy as np
    from ray_tpu.data import block as B
    return B.block_from_numpy_dict({"id": np.arange(lo, hi)})


def _pipe_map(tbl):
    import pyarrow as pa
    return pa.table({"v": pa.compute.multiply(tbl.column("id"), 2)})


def _pipeline_section(cl, blocks, rows=40_000):
    """Owner-tagged map pipeline: generator thunks produce blocks ON the
    cluster (the read_* shape — data is born where tasks run, not shipped
    from the driver), and the executor tags each map task with its input
    block's owner, so blocks never leave the node that produced them. Hit
    rate from the scheduler's locality counters; cross-node block bytes
    from the nodes' direct-pull counters + head staging + head transfer
    counters (all ~0 for a shuffle-free pipeline consumed as refs)."""
    import functools
    from ray_tpu.data.plan import Stats
    from ray_tpu.data.streaming import StreamingExecutor
    from ray_tpu.util import metrics

    def snap():
        nrows = cl.node_rows()
        return (sum(r.get("direct_pull_bytes", 0) for r in nrows
                    if not r.get("is_head")),
                next(r["staged_bytes"] for r in nrows if r.get("is_head")),
                metrics.transfer_bytes_total(),
                metrics.sched_locality_counters())

    pulled0, staged0, xfer0, loc0 = snap()
    thunks = [functools.partial(_pipe_block, i * rows, (i + 1) * rows)
              for i in range(blocks)]
    ex = StreamingExecutor(thunks, [("double", _pipe_map)], Stats())
    nrefs = sum(1 for _ in ex.run(materialize=False))
    assert nrefs == blocks, (nrefs, blocks)

    # node heartbeats carry the counters; give the next beat a moment
    time.sleep(1.5)
    pulled1, staged1, xfer1, loc1 = snap()
    hits = loc1["hits"] - loc0["hits"]
    misses = loc1["misses"] - loc0["misses"]
    total = hits + misses
    return {"blocks": blocks,
            "locality_hits": hits,
            "locality_misses": misses,
            "locality_hit_rate": round(hits / total, 3) if total else 1.0,
            "cross_node_block_bytes": (pulled1 - pulled0)
            + (staged1 - staged0) + (xfer1 - xfer0)}


def _tiered_section(size_mb, reps):
    """Per-tier restore bandwidth (ISSUE 19 spill ladder): MB/s reading a
    blob resident in the shm tier, restoring it whole from the disk
    (spilled) tier, and ranged-reading it straight from the spill file —
    the three sources the pull ladder can land bytes from. Uses a private
    StoreClient so the measurement never races the live session's table."""
    from ray_tpu._private.object_store import StoreClient
    from ray_tpu.util import metrics

    nbytes = size_mb << 20
    blob = os.urandom(nbytes)
    store = StoreClient()
    shm_r, restore_r, ranged_r = [], [], []
    try:
        for rep in range(reps):
            oid = f"tierbench{rep}"
            store.put_raw(oid, blob)
            t0 = time.perf_counter()
            data = bytes(store.read_raw(oid))
            shm_r.append(size_mb / max(time.perf_counter() - t0, 1e-9))
            assert len(data) == nbytes
            del data

            path = store.spill(oid)
            t0 = time.perf_counter()
            step = nbytes // 8
            got = b"".join(store.read_spilled_range(path, i * step, step)
                           for i in range(8))
            ranged_r.append(size_mb / max(time.perf_counter() - t0, 1e-9))
            assert got == blob
            del got

            t0 = time.perf_counter()
            store.restore(oid, path)
            restore_r.append(size_mb / max(time.perf_counter() - t0, 1e-9))
            assert bytes(store.read_raw(oid)) == blob
            store.delete_segment(oid)
    finally:
        store.close()
    sc = metrics.spill_counters()
    return {"size_mb": size_mb,
            "shm_read_mbps_p50": round(_p50(shm_r), 1),
            "disk_restore_mbps_p50": round(_p50(restore_r), 1),
            "disk_ranged_mbps_p50": round(_p50(ranged_r), 1),
            "spill_bytes": sc["spill_bytes"],
            "restore_bytes": sc["restore_bytes"]}


def run_all(size_mb, reps, small_n, blocks):
    cl = _Cluster()
    try:
        rec = {"transfer": _transfer_section(cl, size_mb, reps),
               "batched_get": _batched_get_section(cl, small_n, reps),
               "pipeline": _pipeline_section(cl, blocks),
               "tiered": _tiered_section(size_mb, reps)}
        from ray_tpu.util import metrics
        rec["counters"] = metrics.transfer_counters()
        return rec
    finally:
        cl.close()


def measure():
    out = {"bench": "transfer_dp", "backend": "data-plane",
           "size_mb": SIZE_MB, "reps": REPS, "small_n": SMALL_N,
           "pipe_blocks": PIPE_BLOCKS}
    out.update(run_all(SIZE_MB, REPS, SMALL_N, PIPE_BLOCKS))
    out["speedup"] = out["transfer"]["speedup"]
    print(json.dumps(out))


def smoke():
    """Fast tier-1 hook: parallel-fetch integrity on a small blob, batched
    get ordering, and the locality invariant — tagged map tasks land on
    their block's owner ≥ 90% of the time and move ~no block bytes."""
    rec = {"bench": "transfer_dp_smoke"}
    rec.update(run_all(size_mb=8, reps=1, small_n=16, blocks=4))
    pipe = rec["pipeline"]
    assert pipe["locality_hit_rate"] >= 0.9, pipe
    assert pipe["cross_node_block_bytes"] < (1 << 20), pipe
    assert rec["batched_get"]["batched_s_p50"] > 0
    tier = rec["tiered"]
    assert tier["disk_restore_mbps_p50"] > 0, tier
    assert tier["disk_ranged_mbps_p50"] > 0, tier
    assert tier["restore_bytes"] >= tier["size_mb"] << 20, tier
    print(json.dumps(rec))


if __name__ == "__main__":
    if "--measure" in sys.argv[1:]:
        measure()
    elif "--smoke" in sys.argv[1:]:
        smoke()
    else:
        # parent mode: one --measure child under a timeout, its record
        # persisted, its exit code ours
        from bench import run_measure_child
        sys.exit(run_measure_child(os.path.abspath(__file__)))
