"""Dependency-chain benchmark: prefetching dispatch vs exec-time fetch
(PR 8 tentpole).

A cross-node dependent chain on a real two-host loopback cluster (this
process is the head; a worker-node agent subprocess is its own controller +
shm arena):

  * N producer tasks on the worker node each emit a 16-64 MiB block
    (production is excluded from the measured window — the shape under
    test is sharded data already resident on another host)
  * a serial consumer chain pinned to the head folds the blocks in order:
    c_i = consume(c_{i-1}, block_i)

With `RAY_TPU_PREFETCH=0` (legacy) every consumer's block transfer happens
inside the worker's blocking `get` at execution start, so each chain step
pays compute + transfer. With prefetch on (default) the controller starts
pulling a remote block the moment it is produced and a queued task needs
it, so the transfer overlaps earlier steps' compute and each step pays
~max(compute, residual fetch). `speedup` is legacy_wall / prefetch_wall;
`hit_rate` is prefetch_hits / (hits + misses) counted at dispatch — a hit
means the arg was shm-resident when the exec frame shipped.

Both modes run the SAME build: the knob is read from the environment at
submit/dispatch time, so the comparison isolates the dispatch pipeline,
not a code-version diff.

Modes:
  --measure   real measurement child (run by bench.run_measure_child)
  --smoke     fast CPU correctness check: chain result integrity, hit rate
              >= 0.9, prefetch not slower than legacy (tier-1 test hook)
  --trace     tracing acceptance run (ISSUE 6): prefetch mode with spans
              forced on, exports the head's Chrome trace_event JSON under
              benchmarks/results/ and asserts the span structure — each
              chain task shows disjoint prefetch/exec/publish phases, task
              N+1's prefetch overlaps task N's exec, and phase durations
              cover >= 90% of per-task wall time
  --chaos     health-plane acceptance run (ISSUE 11): kills the worker node
              mid-run and asserts /api/cluster + /api/alerts visibility,
              plus leak-detector attribution of a planted leak; persists
              the record under benchmarks/results/
  (no flag)   parent: runs --measure once under a timeout and persists its
              record under benchmarks/results/

Never imports jax — the dispatch pipeline is accelerator-agnostic; what it
reports are host counts and rates, never device metrics.
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

BLOCK_MB = int(os.environ.get("RAY_TPU_CHAIN_BENCH_MB", 64))
STEPS = int(os.environ.get("RAY_TPU_CHAIN_BENCH_STEPS", 12))
# consumer compute per step; sleep-based so the single-core container can
# run the transfer during it, exactly like a TPU step leaves the host idle.
# Sized a bit above one 64 MiB loopback transfer (~0.11 s on the CI box) so
# the steady state fully hides each fetch inside the previous step's compute
COMPUTE_S = float(os.environ.get("RAY_TPU_CHAIN_BENCH_COMPUTE_S", 0.15))


def _wait_for(pred, timeout, msg):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.2)
    raise TimeoutError("timed out waiting for " + msg)


class _Cluster:
    """Head in-process + one worker-node agent subprocess. The head carries
    a `head_node` marker resource so the consumer chain can be pinned to it
    (otherwise locality-aware placement would move the consumers to the
    data and there would be no cross-node chain to measure)."""

    def __init__(self, head_cpus=2, node_cpus=4):
        import ray_tpu
        self.ray = ray_tpu
        ray_tpu.init(num_cpus=head_cpus, resources={"head_node": 1.0},
                     cluster_port=0)
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

    def close(self):
        if self.node.poll() is None:
            os.killpg(self.node.pid, signal.SIGKILL)
            self.node.wait(timeout=10)
        self.ray.shutdown()


def _run_chain(cl, steps, block_mb, compute_s):
    """Blocks resident on the worker node, serial consumer chain on the
    head; returns (wall_seconds, final_token). The chain is submitted
    upfront so queue admission happens long before each consumer's turn —
    exactly the window prefetch exploits."""
    import numpy as np
    ray = cl.ray
    n = block_mb * (1 << 20) // 8

    @ray.remote(resources={"worker_node": 0.1})
    def produce(i):
        return np.full(n, i, dtype=np.float64)

    @ray.remote(resources={"worker_node": 0.1})
    def barrier(*refs):
        return len(refs)

    @ray.remote(resources={"head_node": 0.01})
    def consume(token, block):
        time.sleep(compute_s)
        # touch both ends: a torn transfer can't pass
        assert block.shape == (n,) and block[0] == block[-1]
        return (0 if token is None else token) + int(block[0])

    # warmup (excluded, like every other bench excludes compile): spawn the
    # node's producer workers and the head's consumer worker, and push one
    # block through the cross-node transfer path, so the measured window is
    # the dispatch pipeline rather than first-task process spawn
    warm_blocks = [produce.remote(0) for _ in range(4)]
    ray.get(consume.remote(None, warm_blocks[0]), timeout=120)
    del warm_blocks

    # dataset production is ALSO excluded: on a single-core CI box the
    # producers' fill+put CPU time would compete with the transfers we are
    # trying to hide and measure noise, not the dispatch pipeline. The
    # measured shape is the common one — sharded data already resident on
    # another host. The barrier task runs ON the node, so waiting for
    # production pulls nothing to the head.
    blocks = [produce.remote(i) for i in range(steps)]
    ray.get(barrier.remote(*blocks), timeout=300)

    t0 = time.perf_counter()
    token = None
    for i in range(steps):
        token = consume.remote(token, blocks[i])
    final = ray.get(token, timeout=300)
    wall = time.perf_counter() - t0
    assert final == sum(range(steps)), final
    del token, blocks
    return wall, final


def _mode(prefetch_on, steps, block_mb, compute_s):
    """One full cluster run in the given mode. The env vars are set before
    the cluster starts so the node agent inherits them too."""
    if prefetch_on:
        os.environ.pop("RAY_TPU_PREFETCH", None)
    else:
        os.environ["RAY_TPU_PREFETCH"] = "0"
    # cap in-flight eager pulls at two blocks: the chain consumes blocks in
    # order, and on a CPU-starved host N concurrent pulls all finish late
    # together (each 1/N the bandwidth) — exactly the admission problem the
    # pull manager's byte cap exists for
    os.environ["RAY_TPU_PREFETCH_MAX_BYTES"] = str(2 * block_mb * (1 << 20))
    cl = _Cluster()
    try:
        wall, _ = _run_chain(cl, steps, block_mb, compute_s)
        from ray_tpu.util import metrics
        counters = metrics.prefetch_counters()
        hit_rate = metrics.prefetch_hit_rate()
    finally:
        cl.close()
        os.environ.pop("RAY_TPU_PREFETCH", None)
        os.environ.pop("RAY_TPU_PREFETCH_MAX_BYTES", None)
    return {"wall_s": round(wall, 3), "counters": counters,
            "hit_rate": round(hit_rate, 3)}


def run_all(steps, block_mb, compute_s):
    legacy = _mode(False, steps, block_mb, compute_s)
    prefetch = _mode(True, steps, block_mb, compute_s)
    return {"steps": steps, "block_mb": block_mb, "compute_s": compute_s,
            "legacy": legacy, "prefetch": prefetch,
            "hit_rate": prefetch["hit_rate"],
            "speedup": round(legacy["wall_s"]
                             / max(prefetch["wall_s"], 1e-9), 2)}


def measure():
    out = {"bench": "chain_dp", "backend": "data-plane"}
    out.update(run_all(STEPS, BLOCK_MB, COMPUTE_S))
    from bench import observability_snapshot
    out["observability"] = observability_snapshot()
    print(json.dumps(out))


def _group_phase_spans(events, name_prefix):
    """task_id -> {phase: (start_s, end_s)} for task_phase events whose
    name starts with `name_prefix` (phase events are named `fn:phase`)."""
    tasks = {}
    for ev in events:
        if ev.get("cat") != "task_phase":
            continue
        if not str(ev.get("name", "")).startswith(name_prefix):
            continue
        a = ev.get("args") or {}
        if not a.get("phase") or not a.get("task_id"):
            continue
        t0 = ev["ts"] / 1e6
        tasks.setdefault(a["task_id"], {})[a["phase"]] = (
            t0, t0 + ev["dur"] / 1e6)
    return tasks


def analyze_trace(events, name_prefix="consume", eps=2e-6):
    """Span-structure report for the chain's consumer tasks:

    - disjoint: within a task, prefetch ends before exec starts and exec
      ends before publish starts (the phases are distinct wall windows,
      not nested guesses)
    - coverage: queued+exec+publish durations >= 90% of the task's
      submit->done wall (prefetch is excluded from the sum — it runs
      UNDER queued by design, that overlap is the thing being measured)
    - overlap: task N+1's prefetch window intersects task N's exec window
      (the dispatch pipeline actually hid the transfer)
    """
    tasks = _group_phase_spans(events, name_prefix)
    rows = sorted((t for t in tasks.values()
                   if "exec" in t and "publish" in t),
                  key=lambda t: t["exec"][0])
    disjoint = coverage_ok = with_prefetch = 0
    for t in rows:
        spans = [t[p] for p in ("prefetch", "exec", "publish") if p in t]
        if all(a[1] <= b[0] + eps for a, b in zip(spans, spans[1:])):
            disjoint += 1
        with_prefetch += "prefetch" in t
        start = t.get("queued", t["exec"])[0]
        covered = sum(b - a for p, (a, b) in t.items() if p != "prefetch")
        if covered >= 0.9 * max(t["publish"][1] - start, 1e-9):
            coverage_ok += 1
    pairs = overlaps = 0
    for prev, nxt in zip(rows, rows[1:]):
        if "prefetch" not in nxt:
            continue
        pairs += 1
        (p0, p1), (e0, e1) = nxt["prefetch"], prev["exec"]
        overlaps += p0 < e1 - eps and p1 > e0 + eps
    return {"tasks": len(rows), "with_prefetch": with_prefetch,
            "disjoint": disjoint, "coverage_ok": coverage_ok,
            "overlap_pairs": pairs, "overlaps": overlaps}


def trace():
    """Tracing acceptance run (ISSUE 6 tentpole criterion): the two-node
    chain with spans forced on; exports Chrome trace JSON and asserts the
    per-phase span structure. Smaller than --measure by default — the
    structure under test is phase geometry, not wall-clock ratios."""
    steps = int(os.environ.get("RAY_TPU_CHAIN_TRACE_STEPS", 8))
    block_mb = int(os.environ.get("RAY_TPU_CHAIN_TRACE_MB", 8))
    compute_s = float(os.environ.get("RAY_TPU_CHAIN_TRACE_COMPUTE_S", 0.02))
    os.environ["RAY_TPU_TRACE"] = "1"
    os.environ["RAY_TPU_TRACE_SAMPLE"] = "1.0"
    os.environ.pop("RAY_TPU_PREFETCH", None)
    # ONE block in flight: with a deeper cap the puller races several tasks
    # ahead of the chain and pull k lands under exec k-2/k-3 — still hidden,
    # but the adjacent-pair geometry the assertion reads (pull N+1 under
    # exec N) needs admission lockstepped to consumption
    os.environ["RAY_TPU_PREFETCH_MAX_BYTES"] = str(block_mb * (1 << 20))
    from ray_tpu.util import tracing
    tracing.refresh()
    cl = _Cluster()
    try:
        wall, _ = _run_chain(cl, steps, block_mb, compute_s)
        from ray_tpu import api
        events = api.timeline()
    finally:
        cl.close()
        os.environ.pop("RAY_TPU_PREFETCH_MAX_BYTES", None)
    from bench import _write_result_artifact
    path = _write_result_artifact("chain_trace", {"traceEvents": events})
    rep = analyze_trace(events)
    rec = {"bench": "chain_trace", "steps": steps, "block_mb": block_mb,
           "compute_s": compute_s, "wall_s": round(wall, 3),
           "events": len(events), "artifact": path, **rep}
    # +1: the warmup consume is traced too; it has no prefetch neighbor
    assert rep["tasks"] >= steps, rec
    assert rep["disjoint"] == rep["tasks"], rec
    assert rep["coverage_ok"] == rep["tasks"], rec
    assert rep["with_prefetch"] >= steps - 1, rec
    assert rep["overlap_pairs"] and rep["overlaps"] >= max(
        1, rep["overlap_pairs"] // 2), rec
    print(json.dumps(rec))


def chaos():
    """Chaos-visibility acceptance run (ISSUE 11): the two-node chain
    cluster with the dashboard up. Plants an intentionally leaked object,
    runs head tasks, then SIGKILLs the worker node mid-flight and asserts:

    - /api/cluster marks the node dead within one heartbeat interval
      (TCP RST from the killed process breaks the head's read loop, so
      detection is near-instant — the heartbeat interval is the bound)
    - /api/alerts carries the node_dead event for that node id
    - the leak detector flags the planted object with its owning task id
      and trace id, surfaced both in /api/cluster leaks and as an
      object_leak alert

    Persists the record under benchmarks/results/ (committed artifact).
    """
    import urllib.request

    # sub-second leak thresholds so the planted leak flags within the run;
    # set before the cluster starts so the head controller reads them
    os.environ["RAY_TPU_LEAK_AGE_S"] = "1.0"
    os.environ["RAY_TPU_LEAK_SCAN_S"] = "0.5"
    from ray_tpu._private.cluster import HEARTBEAT_S
    cl = _Cluster()
    try:
        ray = cl.ray
        from ray_tpu.dashboard import start_dashboard
        _actor, port = start_dashboard(port=0)
        base = f"http://127.0.0.1:{port}"

        def get_json(path):
            with urllib.request.urlopen(base + path, timeout=10) as r:
                return json.loads(r.read().decode())

        @ray.remote(resources={"head_node": 0.01})
        def make_block():
            return b"x" * (1 << 20)

        @ray.remote(resources={"head_node": 0.01})
        def spin(i):
            time.sleep(0.05)
            return i

        # the planted leak: the driver holds this ref for the whole run, so
        # refcount stays >0 long past RAY_TPU_LEAK_AGE_S → "unreleased"
        leak_ref = make_block.remote()
        ray.get(leak_ref, timeout=60)

        node_id = next(n["node_id"] for n in get_json("/api/cluster")["nodes"]
                       if not n["is_head"])

        # head-pinned tasks keep the scheduler busy through the kill (node
        # tasks would hang the run on lineage needing dead-node resources)
        inflight = [spin.remote(i) for i in range(40)]

        os.killpg(cl.node.pid, signal.SIGKILL)
        t_kill = time.perf_counter()
        dead_row = None
        while time.perf_counter() - t_kill < 5 * HEARTBEAT_S:
            rows = get_json("/api/cluster")["nodes"]
            dead_row = next((n for n in rows
                             if n["node_id"] == node_id and not n["alive"]),
                            None)
            if dead_row is not None:
                break
            time.sleep(0.05)
        detect_s = time.perf_counter() - t_kill
        assert dead_row is not None, "killed node never marked dead"
        assert detect_s <= HEARTBEAT_S, (
            f"node-death visible only after {detect_s:.2f}s "
            f"(> heartbeat {HEARTBEAT_S}s)")
        alerts = get_json("/api/alerts")
        node_alerts = [a for a in alerts
                       if a["kind"] == "node_dead" and a["key"] == node_id]
        assert node_alerts, f"no node_dead alert for {node_id}: {alerts}"

        assert ray.get(inflight, timeout=60) == list(range(40))

        # leak visibility: the scan runs on the reaper tick every
        # RAY_TPU_LEAK_SCAN_S once the object is past RAY_TPU_LEAK_AGE_S
        leak = None
        deadline = time.time() + 10
        while time.time() < deadline and leak is None:
            leaks = get_json("/api/cluster")["leaks"]
            leak = next((l for l in leaks
                         if l["object_id"] == leak_ref.id), None)
            if leak is None:
                time.sleep(0.2)
        assert leak is not None, "planted leak never flagged"
        assert leak["reason"] == "unreleased", leak
        assert leak["owner_task"], leak
        assert leak["trace_id"], leak
        leak_alerts = [a for a in get_json("/api/alerts")
                       if a["kind"] == "object_leak"
                       and a["key"] == leak_ref.id]
        assert leak_alerts, "no object_leak alert for the planted leak"

        rec = {"bench": "chaos_health", "heartbeat_s": HEARTBEAT_S,
               "node_id": node_id, "death_detect_s": round(detect_s, 3),
               "dead_row": dead_row,
               "node_dead_alert": node_alerts[0],
               "leak": leak, "leak_alert": leak_alerts[0],
               "alerts_total": len(alerts)}
        from bench import _write_result_artifact
        rec["artifact"] = _write_result_artifact("chaos_health", rec)
        print(json.dumps(rec))
    finally:
        cl.close()
        os.environ.pop("RAY_TPU_LEAK_AGE_S", None)
        os.environ.pop("RAY_TPU_LEAK_SCAN_S", None)


def smoke():
    """Fast tier-1 hook: chain integrity both modes, dispatch-time hit rate
    >= 0.9 with prefetch on, and the overlap direction — prefetch must not
    be slower than legacy beyond noise (hard ratios belong to --measure;
    a loaded single-core CI box makes tight wall-clock asserts flaky)."""
    rec = {"bench": "chain_dp_smoke"}
    rec.update(run_all(steps=5, block_mb=8, compute_s=0.05))
    assert rec["hit_rate"] >= 0.9, rec
    assert rec["prefetch"]["wall_s"] <= rec["legacy"]["wall_s"] * 1.25, rec
    # spill-ladder invariant (ISSUE 19): whatever pressure the run built,
    # the demotion loop must never have spilled a prefetch-pinned object
    from ray_tpu.util import metrics
    sc = metrics.spill_counters()
    rec["spill"] = sc
    assert sc["pinned_demotions"] == 0, sc
    print(json.dumps(rec))


if __name__ == "__main__":
    if "--measure" in sys.argv[1:]:
        measure()
    elif "--smoke" in sys.argv[1:]:
        smoke()
    elif "--trace" in sys.argv[1:]:
        trace()
    elif "--chaos" in sys.argv[1:]:
        chaos()
    else:
        # parent mode: one --measure child under a timeout, its record
        # persisted, its exit code ours
        from bench import run_measure_child
        sys.exit(run_measure_child(os.path.abspath(__file__)))
