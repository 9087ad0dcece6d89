"""Core control-plane benchmark: many-small-tasks throughput + submit
latency, pipelined vs blocking submit (PR 2 tentpole, extended by ISSUE 14).

Measures the cost of the driver→controller control plane with no-op tasks:

  * submit p50/p99 latency per `.remote()` call
  * submit-phase tasks/sec (how fast the driver can issue work)
  * end-to-end tasks/sec (submit + get of all results)
  * blocking controller round trips charged to the submit phase
    (util.metrics.control_roundtrips_total deltas — pipelined submit must
    stay ≤ 1 per N tasks)
  * a worker-side fanout section (a task that itself submits M children),
    exercising the WorkerClient fire-and-forget path over the unix socket
  * per-phase µs breakdown (queued/exec/publish, PR 9 task spans) pulled
    from the state API after the measured burst
  * multi-driver saturation: K subprocess drivers attach to ONE session via
    init(address=...) and burst concurrently — aggregate tasks/sec over the
    union submit window
  * node flatness: the same head-pinned workload with 1 vs 4 loopback node
    agents attached — control-plane throughput must not decay as nodes join

Both modes run in ONE process: the blocking baseline is the same build with
RAY_TPU_SYNC_SUBMIT=1 (the escape-hatch env var), so the comparison isolates
the pipelined control plane rather than a code-version diff. `speedup` is
the pipelined/blocking ratio of submit-phase tasks/sec; `speedup_e2e` is the
same ratio for end-to-end completion.

Burst discipline: the timed submit loop runs `reps` times per init cycle
with a settle sleep before each rep (lets warmup decref batches and publish
traffic drain off the single-core box), and the headline stats come from the
best rep — same min-of-reps reasoning as trace_overhead: the min discards
scheduler-noise outliers, all reps are recorded alongside.

Modes:
  --measure        real measurement child (run by bench.run_measure_child)
  --smoke          fast CPU correctness check: pipelined mode only, asserts
                   the ≤ 1 round-trip invariant (tier-1 test hook)
  --driver-child   internal: one attached driver in the saturation fleet
  (no flag)        parent: runs --measure once under a timeout and
                   persists its record under benchmarks/results/

This bench never imports jax — the control plane is accelerator-agnostic;
what it reports are host counts and rates, never device metrics.
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

N = int(os.environ.get("RAY_TPU_CORE_BENCH_N", 400))
FANOUT_M = int(os.environ.get("RAY_TPU_CORE_BENCH_FANOUT", 32))
NUM_CPUS = int(os.environ.get("RAY_TPU_CORE_BENCH_CPUS", 4))
REPS = int(os.environ.get("RAY_TPU_CORE_BENCH_REPS", 8))
DRIVERS = int(os.environ.get("RAY_TPU_CORE_BENCH_DRIVERS", 2))
# drain window before each timed burst — must outlast the flusher interval
# so leftover warmup/GC batches land before the clock starts
SETTLE_S = float(os.environ.get("RAY_TPU_CORE_BENCH_SETTLE_S", 0.05))


def _percentile(sorted_vals, p):
    return sorted_vals[min(int(len(sorted_vals) * p), len(sorted_vals) - 1)]


def _fanout_fn(m):
    """Runs INSIDE a worker: submit m children and report the blocking
    round trips the submit phase cost this worker process."""
    import ray_tpu
    from ray_tpu.util import metrics

    @ray_tpu.remote
    def _child(i):
        return i

    rt0 = metrics.control_roundtrips_total()
    refs = [_child.remote(i) for i in range(m)]
    submit_rt = metrics.control_roundtrips_total() - rt0
    vals = ray_tpu.get(refs)
    return {"submit_rt": submit_rt, "ok": vals == list(range(m))}


def _phase_breakdown(name: str, limit: int = 4000):
    """Aggregate the PR 9 per-task phase durations (state API `phases`
    dict, seconds) over completed tasks named `name` → µs stats per phase.
    Answers "where does a task's wall time go" next to the tps headline."""
    from ray_tpu.util.state import list_tasks
    per_phase = {}
    counted = 0
    for row in list_tasks(filters=[("name", "=", name)], limit=limit):
        ph = row.get("phases")
        if not ph:
            continue
        counted += 1
        for k, v in ph.items():
            per_phase.setdefault(k, []).append(v * 1e6)
    out = {"tasks": counted}
    for k, vals in sorted(per_phase.items()):
        vals.sort()
        out[k] = {"p50_us": round(_percentile(vals, 0.50), 1),
                  "p99_us": round(_percentile(vals, 0.99), 1),
                  "mean_us": round(sum(vals) / len(vals), 1)}
    return out


def run_mode(sync: bool, n: int, fanout_m: int, reps: int = 1,
             settle_s: float = SETTLE_S):
    """One init→measure→shutdown cycle. `sync` selects the blocking
    baseline via the RAY_TPU_SYNC_SUBMIT escape hatch (read at client
    construction and inherited by workers at spawn). Runs `reps` timed
    bursts and reports the best one (all bursts ride along under
    `submit_tps_all`); `submit_roundtrips` is the max across bursts so the
    pipelining invariant stays conservative."""
    os.environ["RAY_TPU_SYNC_SUBMIT"] = "1" if sync else "0"
    import ray_tpu
    from ray_tpu.util import metrics

    @ray_tpu.remote
    def _noop(i):
        return i

    _fanout = ray_tpu.remote(_fanout_fn)

    ray_tpu.init(num_cpus=NUM_CPUS)
    try:
        # warmup: spawn workers, prime cloudpickle/function caches
        ray_tpu.get([_noop.remote(i) for i in range(8)])

        import gc
        bursts = []
        for _ in range(max(reps, 1)):
            time.sleep(settle_s)
            lat = []
            rt0 = metrics.control_roundtrips_total()
            # GC paused for the timed window only: a collection inside a
            # ~5 ms burst is a multi-hundred-µs stall that lands entirely
            # on p99 — it belongs to the bench process, not the submit path
            gc.disable()
            # Latency is SAMPLED (every 8th call): at ~5 µs/submit the two
            # perf_counter() reads + append were ~0.3 µs of the timed
            # window — bench overhead charged to submit_tps. The stride
            # keeps percentiles honest while the throughput number reflects
            # the submit path, not the measurement.
            remote = _noop.remote
            refs = []
            refs_append = refs.append
            lat_append = lat.append
            perf = time.perf_counter
            t0 = perf()
            for i in range(n):
                if i & 7:
                    refs_append(remote(i))
                else:
                    s = perf()
                    refs_append(remote(i))
                    lat_append(perf() - s)
            t_submit = perf() - t0
            gc.enable()
            submit_rt = metrics.control_roundtrips_total() - rt0
            vals = ray_tpu.get(refs)
            t_e2e = time.perf_counter() - t0
            assert vals == list(range(n)), "wrong results"
            lat.sort()
            bursts.append({
                "submit_p50_us": round(_percentile(lat, 0.50) * 1e6, 1),
                "submit_p99_us": round(_percentile(lat, 0.99) * 1e6, 1),
                "submit_tps": round(n / t_submit, 1),
                "e2e_tps": round(n / t_e2e, 1),
                "submit_roundtrips": submit_rt,
            })
            del refs, vals

        best = max(bursts, key=lambda b: b["submit_tps"])
        phases = _phase_breakdown("_noop")
        fan = ray_tpu.get(_fanout.remote(fanout_m))
        assert fan["ok"], "fanout children returned wrong results"
        return {
            "n": n,
            "reps": len(bursts),
            **best,
            "submit_roundtrips": max(b["submit_roundtrips"] for b in bursts),
            "submit_tps_all": [b["submit_tps"] for b in bursts],
            "phases": phases,
            "fanout": fan,
        }
    finally:
        ray_tpu.shutdown()


# ------------------------------------------------------- ownership model

def ownership_chain(depth: int, reps: int = 3):
    """ISSUE 17 acceptance probe: a depth-k dependent task chain submitted
    and get() by the driver must cost ZERO blocking controller round trips —
    every return object is client-owned (spec.owner_id = "driver"), its
    descriptor is pushed back over the in-process sink, and get() serves
    from the local ownership table (control_local_gets_total counts the
    serves). For contrast the same chain runs with RAY_TPU_OWNERSHIP=0:
    head-owned descriptors force get() through a blocking driver_call."""
    out = {"depth": depth}
    for owned in (True, False):
        os.environ["RAY_TPU_SYNC_SUBMIT"] = "0"
        os.environ["RAY_TPU_OWNERSHIP"] = "1" if owned else "0"
        import ray_tpu
        from ray_tpu.util import metrics
        ray_tpu.init(num_cpus=NUM_CPUS)
        try:
            @ray_tpu.remote
            def _inc(x):
                return x + 1

            ray_tpu.get(_inc.remote(0))  # warmup: spawn + prime caches
            best = None
            for _ in range(max(reps, 1)):
                time.sleep(SETTLE_S)
                rt0 = metrics.control_roundtrips_total()
                lg0 = metrics.control_local_gets_total()
                t0 = time.perf_counter()
                ref = _inc.remote(0)
                for _ in range(depth - 1):
                    ref = _inc.remote(ref)
                val = ray_tpu.get(ref)
                dt = time.perf_counter() - t0
                rec = {
                    "chain_ms": round(dt * 1e3, 2),
                    "roundtrips": metrics.control_roundtrips_total() - rt0,
                    "local_gets": metrics.control_local_gets_total() - lg0,
                }
                assert val == depth, f"chain returned {val}, want {depth}"
                if best is None or rec["chain_ms"] < best["chain_ms"]:
                    best = rec
            out["owned" if owned else "head_owned"] = best
        finally:
            ray_tpu.shutdown()
            os.environ.pop("RAY_TPU_OWNERSHIP", None)
    assert out["owned"]["roundtrips"] == 0, (
        f"ownership chain cost {out['owned']['roundtrips']} blocking round "
        f"trips (client-owned objects must cost zero)")
    return out


def sched_compare(n: int):
    """Native C++ schedule pass (sq_schedule, the ISSUE 17 tentpole) vs the
    Python oracle (RAY_TPU_NATIVE_SCHED=0): same build, same workload —
    the delta is the batched native feasibility/match/claim pass."""
    prev = os.environ.get("RAY_TPU_NATIVE_SCHED")
    try:
        os.environ["RAY_TPU_NATIVE_SCHED"] = "1"
        native = run_mode(sync=False, n=n, fanout_m=4, reps=3)
        os.environ["RAY_TPU_NATIVE_SCHED"] = "0"
        python = run_mode(sync=False, n=n, fanout_m=4, reps=3)
    finally:
        if prev is None:
            os.environ.pop("RAY_TPU_NATIVE_SCHED", None)
        else:
            os.environ["RAY_TPU_NATIVE_SCHED"] = prev
    return {
        "n": n,
        "native": {k: native[k] for k in
                   ("submit_tps", "e2e_tps", "submit_p50_us")},
        "python": {k: python[k] for k in
                   ("submit_tps", "e2e_tps", "submit_p50_us")},
        "e2e_speedup": round(native["e2e_tps"] /
                             max(python["e2e_tps"], 1e-9), 2),
    }


# ------------------------------------------------- multi-driver saturation

def _driver_child(n: int):
    """One attached driver in the saturation fleet: join the parent's
    session over RAY_TPU_ADDRESS, burst n submits, report the absolute
    submit window so the parent can compute fleet-aggregate tps."""
    os.environ["RAY_TPU_SYNC_SUBMIT"] = "0"
    import ray_tpu
    ray_tpu.init(address="auto")
    try:
        @ray_tpu.remote
        def _noop(i):
            return i

        ray_tpu.get([_noop.remote(i) for i in range(8)])
        time.sleep(SETTLE_S)
        w0 = time.time()
        t0 = time.perf_counter()
        refs = [_noop.remote(i) for i in range(n)]
        t_submit = time.perf_counter() - t0
        vals = ray_tpu.get(refs)
        t_e2e = time.perf_counter() - t0
        assert vals == list(range(n)), "wrong results in attached driver"
        print(json.dumps({
            "n": n, "window": [w0, w0 + t_e2e],
            "submit_tps": round(n / t_submit, 1),
            "e2e_tps": round(n / t_e2e, 1)}), flush=True)
    finally:
        ray_tpu.shutdown()


def multi_driver(k: int, n_per_driver: int):
    """Saturation mode: this process hosts the session, K subprocess
    drivers attach and burst concurrently. Aggregate tps is the fleet's
    total tasks over the union of the drivers' e2e windows — the number
    that tells you whether one extra submitting process buys throughput or
    just contends on the controller loop."""
    os.environ["RAY_TPU_SYNC_SUBMIT"] = "0"
    import ray_tpu
    ray_tpu.init(num_cpus=NUM_CPUS)
    procs = []
    try:
        env = dict(os.environ)
        env["RAY_TPU_CORE_BENCH_N"] = str(n_per_driver)
        for _ in range(k):
            procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--driver-child"],
                env=env, stdout=subprocess.PIPE, stdin=subprocess.DEVNULL,
                text=True))
        drivers = []
        for p in procs:
            out, _ = p.communicate(timeout=180)
            if p.returncode != 0:
                raise RuntimeError(f"driver child exited {p.returncode}")
            drivers.append(json.loads(out.strip().splitlines()[-1]))
        total = sum(d["n"] for d in drivers)
        w0 = min(d["window"][0] for d in drivers)
        w1 = max(d["window"][1] for d in drivers)
        return {"drivers": k, "n_per_driver": n_per_driver,
                "aggregate_e2e_tps": round(total / max(w1 - w0, 1e-9), 1),
                "per_driver": drivers}
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        ray_tpu.shutdown()


# ------------------------------------------------------- node flatness

def _wait_for(pred, timeout, msg):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.2)
    raise TimeoutError("timed out waiting for " + msg)


def _cluster_e2e(num_agents: int, n: int, reps: int = 12):
    """Head + `num_agents` loopback node agents; the workload is pinned to
    the head so compute stays constant — what varies is only the
    control-plane load the extra nodes add (heartbeats, holds-object
    traffic, directory fan-in). Returns head-side e2e tps."""
    import ray_tpu
    ray_tpu.init(num_cpus=2, resources={"head_node": 1.0}, cluster_port=0)
    procs = []
    try:
        addr = ray_tpu.cluster_address()
        env = dict(os.environ)
        env.pop("RAY_TPU_ARENA", None)   # each node is its own session
        env.pop("RAY_TPU_ADDRESS", None)
        for _ in range(num_agents):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "ray_tpu._private.node_main",
                 "--address", addr, "--num-cpus", "1",
                 "--resources", '{"worker_node": 1}'],
                env=env, stdin=subprocess.DEVNULL, start_new_session=True))
        _wait_for(lambda: len(ray_tpu.nodes()) == num_agents + 1, 120,
                  f"{num_agents} node registrations")

        @ray_tpu.remote(resources={"head_node": 0.01})
        def _noop(i):
            return i

        ray_tpu.get([_noop.remote(i) for i in range(8)])
        submit_tps, best_e2e = [], 0.0
        # Per-rep samples: on a small host the submit window (~1 ms) is
        # shorter than an OS scheduling quantum, so any single rep is a
        # lottery on whether the controller loop / node heartbeats preempt
        # the submitting thread mid-window. The caller aggregates samples
        # across interleaved cycles — the MEDIAN rep is the flatness
        # signal (a single lucky window in one config must not swing the
        # ratio), the max is reported as the peak.
        for _ in range(reps):
            time.sleep(SETTLE_S)
            t0 = time.perf_counter()
            refs = [_noop.remote(i) for i in range(n)]
            t_submit = time.perf_counter() - t0
            vals = ray_tpu.get(refs)
            t_e2e = time.perf_counter() - t0
            assert vals == list(range(n)), "wrong results under cluster"
            submit_tps.append(n / t_submit)
            best_e2e = max(best_e2e, n / t_e2e)
        return {"nodes": num_agents + 1, "n": n,
                "submit_tps_reps": submit_tps,
                "e2e_tps": round(best_e2e, 1)}
    finally:
        for p in procs:
            if p.poll() is None:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                    p.wait(timeout=10)
                except (ProcessLookupError, subprocess.TimeoutExpired):
                    pass
        ray_tpu.shutdown()


def node_flatness(n: int):
    """Acceptance probe (ISSUE 17): submit tasks/sec with 1 vs 8 attached
    loopback node agents. A sharded directory + codec'd heartbeat plane
    should hold the submit rate flat — `flatness_8v1` (1-agent tps over
    8-agent tps) must stay ≤ 1.05; a global-lock control plane decays as
    nodes multiply. e2e tps rides along but is NOT the flatness signal —
    on a small host it measures CPU contention from the extra agent
    processes, not the control plane.

    The two configs run in ALTERNATING cycles (1, 8, 1, 8, ...), pooling
    per-rep samples per config: shared-host noise (steal time, neighbor
    load) drifts over tens of seconds, so back-to-back blocks would hand
    one config a systematically slow phase and swing the ratio either
    way run-to-run. Flatness compares the MEDIAN rep per config (robust
    to both preempted and once-in-a-run lucky windows); the max rides
    along as submit_tps_peak."""
    import statistics
    one = {"nodes": 2, "n": n, "e2e_tps": 0.0, "reps": []}
    eight = {"nodes": 9, "n": n, "e2e_tps": 0.0, "reps": []}
    for _ in range(3):
        for agents, agg in ((1, one), (8, eight)):
            cyc = _cluster_e2e(agents, n, reps=4)
            agg["reps"].extend(cyc["submit_tps_reps"])
            agg["e2e_tps"] = max(agg["e2e_tps"], cyc["e2e_tps"])
    for agg in (one, eight):
        reps = agg.pop("reps")
        agg["submit_tps"] = round(statistics.median(reps), 1)
        agg["submit_tps_peak"] = round(max(reps), 1)
    return {"runs": [one, eight],
            "tps_ratio_8v1": round(eight["submit_tps"] /
                                   max(one["submit_tps"], 1e-9), 3),
            "flatness_8v1": round(one["submit_tps"] /
                                  max(eight["submit_tps"], 1e-9), 3),
            "e2e_ratio_8v1": round(eight["e2e_tps"] /
                                   max(one["e2e_tps"], 1e-9), 3)}


def _set_trace(on: bool):
    """Flip tracing for the NEXT init cycle: the env var is what spawned
    workers inherit; refresh() re-reads it for this (driver) process."""
    os.environ["RAY_TPU_TRACE"] = "1" if on else "0"
    from ray_tpu.util import tracing
    tracing.refresh()


def trace_overhead(n: int, reps: int = 2):
    """Submit-latency cost of span annotation: pipelined mode with tracing
    forced ON vs OFF, interleaved off/on reps, best-of-reps p50 each (the
    min discards scheduler-noise outliers — the signal is a sub-µs adder).
    Restores the ambient RAY_TPU_TRACE afterwards."""
    prev = os.environ.get("RAY_TPU_TRACE")
    p50 = {False: [], True: []}
    try:
        for _ in range(reps):
            for on in (False, True):
                _set_trace(on)
                p50[on].append(
                    run_mode(sync=False, n=n, fanout_m=4)["submit_p50_us"])
    finally:
        if prev is None:
            os.environ.pop("RAY_TPU_TRACE", None)
        else:
            os.environ["RAY_TPU_TRACE"] = prev
        from ray_tpu.util import tracing
        tracing.refresh()
    off, on = min(p50[False]), min(p50[True])
    return {"n": n, "reps": reps,
            "submit_p50_off_us": off, "submit_p50_on_us": on,
            "p50_off_all_us": p50[False], "p50_on_all_us": p50[True],
            "overhead_ratio": round(on / max(off, 1e-9), 3)}


def health_overhead(n: int, reps: int = 2):
    """Submit-latency cost of the health signal plane (ISSUE 11): pipelined
    mode with RAY_TPU_HEALTH forced OFF vs ON (the default), interleaved
    reps, best-of-reps p50 each — same discipline as trace_overhead. The
    monitor reads the env per tick, but flipping before init also covers
    the heartbeat payload on spawned agents."""
    prev = os.environ.get("RAY_TPU_HEALTH")
    p50 = {False: [], True: []}
    try:
        for _ in range(reps):
            for on in (False, True):
                os.environ["RAY_TPU_HEALTH"] = "1" if on else "0"
                p50[on].append(
                    run_mode(sync=False, n=n, fanout_m=4)["submit_p50_us"])
    finally:
        if prev is None:
            os.environ.pop("RAY_TPU_HEALTH", None)
        else:
            os.environ["RAY_TPU_HEALTH"] = prev
    off, on = min(p50[False]), min(p50[True])
    return {"n": n, "reps": reps,
            "submit_p50_off_us": off, "submit_p50_on_us": on,
            "p50_off_all_us": p50[False], "p50_on_all_us": p50[True],
            "overhead_ratio": round(on / max(off, 1e-9), 3)}


def measure():
    from bench import observability_snapshot  # repo root on sys.path
    from ray_tpu._native import codec as _codec
    from ray_tpu._native import objdir as _objdir
    # throwaway cycle: pay one-time import/worker-spawn warmness before
    # either timed mode (ordering would otherwise favor whichever runs
    # second)
    run_mode(sync=False, n=8, fanout_m=4)
    out = {"bench": "core_control_plane", "backend": "control-plane",
           "n": N, "fanout_m": FANOUT_M, "num_cpus": NUM_CPUS,
           "native": {"codec": _codec.native_available(),
                      "obj_directory": _objdir.available(),
                      "wire_version": _codec.wire_version()}}
    out["blocking"] = run_mode(sync=True, n=N, fanout_m=FANOUT_M, reps=2)
    out["pipelined"] = run_mode(sync=False, n=N, fanout_m=FANOUT_M, reps=REPS)
    out["speedup"] = round(
        out["pipelined"]["submit_tps"] / max(out["blocking"]["submit_tps"],
                                             1e-9), 2)
    out["speedup_e2e"] = round(
        out["pipelined"]["e2e_tps"] / max(out["blocking"]["e2e_tps"],
                                          1e-9), 2)
    out["ownership"] = ownership_chain(depth=16)
    out["sched_compare"] = sched_compare(n=N)
    out["multi_driver"] = multi_driver(k=DRIVERS, n_per_driver=N)
    out["node_flatness"] = node_flatness(n=200)
    out["tracing_overhead"] = trace_overhead(N, reps=2)
    out["health_overhead"] = health_overhead(N, reps=2)
    out["observability"] = observability_snapshot()
    print(json.dumps(out))


def smoke():
    """Fast tier-1 hook: pipelined mode only, asserts the control-plane
    invariant (≤ 1 blocking round trip for the whole submit phase, driver
    AND worker side)."""
    n = int(os.environ.get("RAY_TPU_CORE_BENCH_N", 32))
    rec = run_mode(sync=False, n=n, fanout_m=8)
    assert rec["submit_roundtrips"] <= 1, (
        f"pipelined submit cost {rec['submit_roundtrips']} round trips "
        f"for {n} tasks (expected ≤ 1)")
    assert rec["fanout"]["submit_rt"] <= 1, (
        f"worker fanout submit cost {rec['fanout']['submit_rt']} round "
        f"trips (expected ≤ 1)")
    # tracing-overhead invariant (ISSUE 6): span annotation on the submit
    # hot path must cost < 5% of submit p50. The 2 µs absolute grace keeps
    # a sub-30 µs baseline from failing on timer quantization alone — 5%
    # of 19 µs is under one scheduler tick on a loaded CI box.
    ov = trace_overhead(n=max(n * 4, 128), reps=2)
    off, on_ = ov["submit_p50_off_us"], ov["submit_p50_on_us"]
    assert on_ <= max(off * 1.05, off + 2.0), (
        f"tracing overhead too high: p50 {off} -> {on_} us ({ov})")
    rec["tracing_overhead"] = ov
    # health-gauge invariant (ISSUE 11): the signal plane must cost < 2%
    # of submit p50 — the gauges live on the 1s reaper tick and the
    # heartbeat, not on the submit path, so this guards against anything
    # leaking into the hot path. Same 2 µs quantization grace as above.
    hv = health_overhead(n=max(n * 4, 128), reps=2)
    off, on_ = hv["submit_p50_off_us"], hv["submit_p50_on_us"]
    assert on_ <= max(off * 1.02, off + 2.0), (
        f"health-gauge overhead too high: p50 {off} -> {on_} us ({hv})")
    rec["health_overhead"] = hv
    # ownership invariant (ISSUE 17): a driver-local small-object chain
    # costs ZERO blocking round trips — asserted inside ownership_chain
    rec["ownership"] = ownership_chain(depth=8, reps=1)
    print(json.dumps({"bench": "core_control_plane_smoke", **rec}))


if __name__ == "__main__":
    if "--measure" in sys.argv[1:]:
        measure()
    elif "--smoke" in sys.argv[1:]:
        smoke()
    elif "--driver-child" in sys.argv[1:]:
        _driver_child(int(os.environ.get("RAY_TPU_CORE_BENCH_N", 400)))
    else:
        # parent mode: one --measure child under a timeout, its record
        # persisted, its exit code ours
        from bench import run_measure_child
        sys.exit(run_measure_child(os.path.abspath(__file__)))
