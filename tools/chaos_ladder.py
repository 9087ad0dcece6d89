"""Chaos ladder: kill a worker node mid-run across the four workload
shapes the repo benchmarks (transfer / pipeline / sebulba / serving) and
prove the resilience stack end to end:

  * every rung COMPLETES CORRECTLY after the kill — lost objects are
    reconstructed from lineage (or retried) transparently at get() time;
  * the chaos run's wall clock stays within 3x the no-fault baseline of
    the same workload (recovery is re-execution, not a hang);
  * recovery cost is visible per phase in the head timeline
    (`python -m ray_tpu timeline`): recover.detect / recover.reconstruct
    windows from the lineage plane, reconcile.replace /
    reconcile.recovered from the autoscaler reconciler;
  * a dedicated reconcile rung kills a provider-launched node and asserts
    the reconciler turns the node_dead alert into a create_node within
    two heartbeat intervals, with the alert-id -> create causality
    recorded.

Modes:
  --measure   full ladder: baseline + chaos per rung, one combined
              artifact under benchmarks/results/
  --smoke     fast tier-1 gate: one kill-mid-run rung + the reconcile
              rung, correctness asserts only (wall-clock ratios are for
              --measure; a loaded CI box makes them flaky)
  (no flag)   parent: runs --measure once under a timeout (bench.py's
              run_measure_child)

Never imports jax — faults live in the control/data planes.
"""

import json
import os
import signal
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# keep ray_tpu.init() from importing jax for chip discovery
os.environ.setdefault("RAY_TPU_NUM_CHIPS", "0")

BLOCK_KB = int(os.environ.get("RAY_TPU_CHAOS_LADDER_KB", 2048))
TASK_S = float(os.environ.get("RAY_TPU_CHAOS_LADDER_TASK_S", 0.15))
SLOWDOWN_BUDGET = 3.0


def _wait_for(pred, timeout, msg):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.1)
    raise TimeoutError("timed out waiting for " + msg)


class _Cluster:
    """Head in-process + one worker-node agent subprocess (the
    chain_bench topology: two controllers, two shm arenas, one cluster)."""

    def __init__(self, head_cpus=2, node_cpus=2):
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
        self.node_id = next(r["node_id"] for r in ray_tpu.nodes()
                            if r["resources"].get("worker_node"))

    def kill_node(self):
        """SIGKILL the node's whole process group: agent + its workers die
        uncleanly, the head sees the TCP RST and fails over."""
        os.killpg(self.node.pid, signal.SIGKILL)
        _wait_for(lambda: len(self.ray.nodes()) == 1, 40, "death detection")

    def soft_affinity(self):
        """Prefer the node while alive, fall back to the head once it is
        dead — so reconstruction always has somewhere feasible to run."""
        from ray_tpu.util.scheduling_strategies import (
            NodeAffinitySchedulingStrategy)
        return NodeAffinitySchedulingStrategy(node_id=self.node_id, soft=True)

    def close(self):
        if self.node.poll() is None:
            os.killpg(self.node.pid, signal.SIGKILL)
            self.node.wait(timeout=10)
        self.ray.shutdown()


# ------------------------------------------------------------------- rungs
#
# Each rung parks intermediate results on the worker node, optionally
# SIGKILLs it mid-run (after half the results are consumed), then verifies
# every final value — identical math in baseline and chaos runs.

def _rung_transfer(cl, kill):
    """transfer_bench shape: blocks produced on the node, pulled one by
    one to the driver over the data plane; the kill lands between pulls,
    so later gets() reconstruct instead of pulling."""
    import numpy as np
    ray = cl.ray
    n_blocks, n = 6, BLOCK_KB * 1024 // 8
    strat = cl.soft_affinity()

    @ray.remote(num_cpus=0.5)
    def produce(i):
        time.sleep(TASK_S)
        return np.full(n, float(i))

    refs = [produce.options(scheduling_strategy=strat).remote(i)
            for i in range(n_blocks)]
    for i, ref in enumerate(refs):
        if kill and i == n_blocks // 2:
            cl.kill_node()
        out = ray.get(ref, timeout=120)
        assert out.shape == (n,) and float(out[0]) == float(i), (i, out[:3])
    return n_blocks


def _rung_pipeline(cl, kill):
    """pipeline_bench shape: two dependent stages per lane on the node,
    folded on the head — the kill loses BOTH stages' outputs, so recovery
    walks the lineage recursively (stage2 needs stage1 re-run first)."""
    import numpy as np
    ray = cl.ray
    lanes, n = 4, BLOCK_KB * 1024 // 8
    strat = cl.soft_affinity()

    @ray.remote(num_cpus=0.5)
    def stage1(i):
        time.sleep(TASK_S)
        return np.full(n, float(i))

    @ray.remote(num_cpus=0.5)
    def stage2(a):
        time.sleep(TASK_S / 2)
        return a * 2.0 + 1.0

    @ray.remote(resources={"head_node": 0.01})
    def fold(a):
        return float(a[0]) + float(a[-1])

    outs = [stage2.options(scheduling_strategy=strat).remote(
        stage1.options(scheduling_strategy=strat).remote(i))
        for i in range(lanes)]
    finals = []
    for i, ref in enumerate(outs):
        if kill and i == lanes // 2:
            cl.kill_node()
        finals.append(ray.get(fold.remote(ref), timeout=120))
    assert finals == [2.0 * (2.0 * i + 1.0) for i in range(lanes)], finals
    return lanes


def _rung_sebulba(cl, kill):
    """sebulba shape: rollout batches produced on the node (actor-side of
    the RL pipeline), a learner step on the head folds each batch; the
    kill lands between learner steps, so later batches reconstruct."""
    import numpy as np
    ray = cl.ray
    batches, per_batch, n = 4, 2, BLOCK_KB * 1024 // 8
    strat = cl.soft_affinity()

    @ray.remote(num_cpus=0.5)
    def rollout(b, j):
        time.sleep(TASK_S)
        return np.full(n, float(b * per_batch + j))

    @ray.remote(resources={"head_node": 0.01})
    def learn(*trajs):
        return sum(float(t[0]) for t in trajs)

    plan = [[rollout.options(scheduling_strategy=strat).remote(b, j)
             for j in range(per_batch)] for b in range(batches)]
    total = 0.0
    for b, batch in enumerate(plan):
        if kill and b == batches // 2:
            cl.kill_node()
        total += ray.get(learn.remote(*batch), timeout=120)
    expect = float(sum(range(batches * per_batch)))
    assert total == expect, (total, expect)
    return batches


def _rung_serving(cl, kill):
    """serving shape: a stream of small requests routed at the node; the
    kill lands while requests are IN FLIGHT, so the dead node's running
    tasks are retried rather than reconstructed (results are inline)."""
    ray = cl.ray
    n_req = 24
    strat = cl.soft_affinity()

    @ray.remote(num_cpus=0.5)
    def request(i):
        time.sleep(TASK_S / 3)
        return i * i

    refs = [request.options(scheduling_strategy=strat).remote(i)
            for i in range(n_req)]
    if kill:
        cl.kill_node()  # immediately: most requests still queued/running
    got = ray.get(refs, timeout=120)
    assert got == [i * i for i in range(n_req)], got
    return n_req


def _rung_spill(cl, kill):
    """tiered-memory shape (ISSUE 19): the head's store is caught
    mid-ladder — every driver-owned block force-demoted to the disk tier —
    when the worker node dies. Driver-owned blocks must come back via
    restore-from-disk, node-held blocks via lineage reconstruction; the
    run must never hang, and the pressure loop must never have demoted a
    prefetch-pinned object."""
    import asyncio

    import numpy as np

    from ray_tpu import api
    from ray_tpu.util import metrics
    ray = cl.ray
    n_blocks, n = 4, BLOCK_KB * 1024 // 8
    strat = cl.soft_affinity()

    @ray.remote(num_cpus=0.5)
    def produce(i):
        time.sleep(TASK_S)
        return np.full(n, float(i))

    node_refs = [produce.options(scheduling_strategy=strat).remote(i)
                 for i in range(n_blocks)]
    puts = [ray.put(np.full(n, 100.0 + i)) for i in range(n_blocks)]
    ray.wait(node_refs, num_returns=n_blocks, timeout=120)

    rt = api._runtime
    rt.client.flush()

    async def demote_all():
        c = rt.controller
        for _ in range(300):
            if all(c.objects.get(r.id) is not None
                   and c.objects[r.id].location == "shm" for r in puts):
                break
            await asyncio.sleep(0.02)
        c._spill_down(0, pressure=True)
        return [c.objects[r.id].location for r in puts]

    locs = asyncio.run_coroutine_threadsafe(demote_all(), rt.loop).result(60)
    assert all(loc == "spilled" for loc in locs), locs

    sc0 = metrics.spill_counters()
    if kill:
        cl.kill_node()
    # restore-from-disk: driver-owned blocks come back bit-identical
    for i, got in enumerate(ray.get(puts, timeout=120)):
        assert float(got[0]) == 100.0 + i and got.shape == (n,), (i, got[:3])
    # lineage: node-held blocks reconstruct (or were already shipped)
    for i, got in enumerate(ray.get(node_refs, timeout=120)):
        assert float(got[0]) == float(i) and got.shape == (n,), (i, got[:3])
    sc1 = metrics.spill_counters()
    assert sc1["restored_objects"] - sc0["restored_objects"] >= n_blocks, (
        sc0, sc1)
    assert sc1["pinned_demotions"] == 0, sc1
    return 2 * n_blocks


_RUNGS = [("transfer", _rung_transfer), ("pipeline", _rung_pipeline),
          ("sebulba", _rung_sebulba), ("serving", _rung_serving),
          ("spill", _rung_spill)]


def _recovery_windows(node_id=None, prefix=None):
    """Pull the recovery-phase spans out of the head timeline — the same
    events `python -m ray_tpu timeline` exports (cat == "recovery").
    The trace ring is process-wide, so filter to this rung's dead node
    (or span-name prefix) to keep each record self-describing."""
    from ray_tpu import api
    out = []
    for ev in api.timeline():
        if ev.get("cat") != "recovery":
            continue
        args = ev.get("args") or {}
        if node_id is not None and args.get("node_id") != node_id:
            continue
        if prefix is not None and not str(ev.get("name", "")).startswith(prefix):
            continue
        out.append({"name": ev.get("name"),
                    "dur_s": round(ev.get("dur", 0) / 1e6, 4),
                    "args": args})
    return out


def _run_rung(name, fn, kill):
    from ray_tpu.util import metrics
    recon0 = metrics._counter_total("reconstructions_total")
    cl = _Cluster()
    try:
        t0 = time.perf_counter()
        units = fn(cl, kill)
        wall = time.perf_counter() - t0
        rec = {"wall_s": round(wall, 3), "units": units, "killed": kill}
        if kill:
            rec["recovery_windows"] = _recovery_windows(node_id=cl.node_id)
            rec["reconstructions"] = (
                metrics._counter_total("reconstructions_total") - recon0)
            # process-lifetime transfer totals (retry/deadline visibility)
            rec["transfer_totals"] = metrics.transfer_counters()
        return rec
    finally:
        cl.close()


def _rung_reconcile():
    """Alert-driven replacement: a provider-launched node is SIGKILLed;
    the head reconciler must consume the node_dead alert and create_node a
    replacement within two heartbeat intervals, with the causality chain
    (alert id -> terminate_dead -> replace -> recovered) on record."""
    import ray_tpu
    from ray_tpu._private import state
    from ray_tpu._private.cluster import HEARTBEAT_S
    from ray_tpu.autoscaler import SubprocessNodeProvider, sdk

    ray_tpu.init(num_cpus=2, resources={"head_node": 1.0}, cluster_port=0)
    provider = SubprocessNodeProvider(
        cpus_per_node=2.0, extra_resources={"worker_node": 1.0})
    try:
        sdk.set_node_provider(provider, max_nodes=2)
        ctrl = state.global_client().controller
        assert ctrl.reconciler is not None, "reconciler not installed"
        handle = provider.create_node({"CPU": 2.0}, ray_tpu.cluster_address())
        ctrl._provider_nodes[handle] = {"CPU": 2.0}  # as _create would
        _wait_for(lambda: len(ray_tpu.nodes()) == 2, 60, "node registration")
        dead_pid = provider.pid_of(handle)

        t_kill = time.time()
        os.killpg(dead_pid, signal.SIGKILL)
        _wait_for(lambda: len(ray_tpu.nodes()) == 1, 10 * HEARTBEAT_S,
                  "death detection")
        # replacement registered = back to 2 live nodes with a NEW agent pid
        _wait_for(lambda: len(ray_tpu.nodes()) == 2, 30 * HEARTBEAT_S,
                  "replacement node registration")
        _wait_for(lambda: any(e["action"] == "recovered"
                              for e in ctrl.reconciler.status()["events"]),
                  15 * HEARTBEAT_S, "reconciler recovered record")

        st = ctrl.reconciler.status()
        events = st["events"]
        alert = next(ev for ev in ctrl.health.alerts.events()
                     if ev["kind"] == "node_dead")
        replace = next(e for e in events if e["action"] == "replace")
        recovered = next(e for e in events if e["action"] == "recovered")
        assert replace["alert_id"] == alert["id"], (replace, alert)
        assert recovered["alert_id"] == alert["id"], (recovered, alert)
        assert any(e["action"] == "terminate_dead" and e["handle"] == handle
                   for e in events), events
        replace_latency = replace["ts"] - alert["ts"]
        assert replace_latency <= 2 * HEARTBEAT_S, (
            f"replacement took {replace_latency:.2f}s "
            f"(> 2 heartbeats = {2 * HEARTBEAT_S}s)")
        return {"heartbeat_s": HEARTBEAT_S,
                "detect_s": round(alert["ts"] - t_kill, 3),
                "replace_latency_s": round(replace_latency, 3),
                "recovered_latency_s": round(recovered["ts"] - alert["ts"], 3),
                "replacements": st["replacements"],
                "events": events,
                "recovery_windows": _recovery_windows(prefix="reconcile.")}
    finally:
        provider.shutdown()
        ray_tpu.shutdown()


def _rung_fleet():
    """Serve-fleet rung (ISSUE 20): SIGKILL one of three replicas under
    load. Gates — zero failed requests (the handle retries on a survivor),
    bounded p99 during the chaos burst, and after one handle refresh
    interval the controller has pruned the corpse so no request pays a
    died-retry again."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.controller import get_controller
    from ray_tpu.util import metrics

    ray_tpu.init(num_cpus=6, cluster_port=0)
    try:
        @serve.deployment(num_replicas=3, max_ongoing_requests=8)
        class Echo:
            def ping(self, i):
                time.sleep(0.01)
                return i

        h = serve.run(Echo.bind(), name="chaos-fleet")
        hp = h.options(method_name="ping")

        def burst(n):
            t0 = time.time()
            resps = [hp.remote(i) for i in range(n)]
            failed, lats = 0, []
            for i, r in enumerate(resps):
                t1 = time.time()
                try:
                    assert r.result(timeout_s=60) == i
                except Exception:  # noqa: BLE001 - counted by the gate
                    failed += 1
                lats.append(time.time() - t1)
            lats.sort()
            return {"n": n, "failed": failed, "wall_s": time.time() - t0,
                    "p99_s": round(lats[int(len(lats) * 0.99)
                                        if len(lats) > 1 else -1], 4)}

        base = burst(40)
        ctrl = get_controller()
        reps = ray_tpu.get(ctrl.get_replicas.remote("chaos-fleet", "Echo"))
        victim_pid = ray_tpu.get(reps[0].stats.remote())["pid"]
        os.kill(victim_pid, signal.SIGKILL)
        chaos = burst(40)
        d_mid = metrics.serve_fleet_counters()["died_retries"]
        # > handle refresh TTL (0.5s) + death-report round trip: every
        # handle's next pick must come from the pruned survivor list
        time.sleep(0.8)
        steady = burst(30)
        d_end = metrics.serve_fleet_counters()["died_retries"]
        survivors = len(ray_tpu.get(
            ctrl.get_replicas.remote("chaos-fleet", "Echo")))
        rec = {"baseline": base, "chaos": chaos, "steady": steady,
               "died_retries": round(d_mid),
               "died_retries_after_refresh": round(d_end - d_mid),
               "survivors": survivors}
        assert chaos["failed"] == 0 and steady["failed"] == 0, rec
        assert d_mid >= 1, rec                       # the kill was felt
        assert rec["died_retries_after_refresh"] == 0, rec  # corpse pruned
        assert survivors == 2, rec
        assert chaos["p99_s"] <= max(5 * base["p99_s"], 2.0), rec
        serve.shutdown()
        return rec
    finally:
        ray_tpu.shutdown()


# ------------------------------------------------------------------- modes

def run_ladder(rungs=None):
    out = {}
    for name, fn in (rungs or _RUNGS):
        base = _run_rung(name, fn, kill=False)
        chaos = _run_rung(name, fn, kill=True)
        slowdown = round(chaos["wall_s"] / max(base["wall_s"], 1e-9), 2)
        out[name] = {"baseline": base, "chaos": chaos,
                     "slowdown": slowdown,
                     "ok": slowdown <= SLOWDOWN_BUDGET}
    out["reconcile"] = _rung_reconcile()
    out["fleet"] = _rung_fleet()
    return out


def measure():
    from bench import _write_result_artifact
    rec = {"bench": "chaos_ladder", "backend": "chaos",
           "block_kb": BLOCK_KB, "task_s": TASK_S,
           "slowdown_budget": SLOWDOWN_BUDGET}
    rec.update(run_ladder())
    rec["artifact"] = _write_result_artifact("chaos_ladder", rec)
    print(json.dumps(rec))


def smoke():
    """Tier-1 chaos gate: one kill-mid-run rung must complete correctly
    (reconstruction) and the reconciler must replace a killed provider
    node — correctness only, no wall-clock ratios."""
    rec = {"bench": "chaos_ladder_smoke"}
    rec["transfer"] = _run_rung("transfer", _rung_transfer, kill=True)
    assert rec["transfer"]["reconstructions"] >= 1, rec
    # kill-mid-spill (ISSUE 19): restore-from-disk + lineage, never hangs
    rec["spill"] = _run_rung("spill", _rung_spill, kill=True)
    rec["reconcile"] = _rung_reconcile()
    # serve-fleet kill (ISSUE 20): re-route on survivor, corpse pruned
    # within one refresh interval, zero failed requests
    rec["fleet"] = _rung_fleet()
    print(json.dumps(rec))


if __name__ == "__main__":
    if "--measure" in sys.argv[1:]:
        measure()
    elif "--smoke" in sys.argv[1:]:
        smoke()
    else:
        from bench import run_measure_child
        sys.exit(run_measure_child(os.path.abspath(__file__)))
