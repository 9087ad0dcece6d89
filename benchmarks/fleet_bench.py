"""Production serve fleet under open-loop load (ISSUE 20 tentpole part 3):
Poisson arrivals with a diurnal burst and heavy-tailed prompt/output
lengths against >= 3 loopback LLM replicas.

Two sections, one JSON record:

  routing    the SAME workload (same seed, same arrival times) twice at
             equal offered load — prefix-affinity routing vs the p2c
             baseline (RAY_TPU_PREFIX_AFFINITY=0). Per-replica KV pools
             are sized so one replica holds its affinity share of the
             prompt families comfortably but thrashes under p2c's
             everything-everywhere spread (the tiered-bench working-set
             trick applied fleet-wide). Reports sustained RPS, server
             TTFT p50/p99 (slot-queue time included), client TPOT p99,
             goodput under the TTFT SLO, the fleet prefix-cache hit rate
             per mode, the handle's affinity hit/miss/spill counters, and
             the engine's own phase account summed over the replicas
             (`stats()["decode"]["phase_s"/"phase_n"]`: decode_sync,
             prefill_dispatch, admit_allocate, demote, ...).
  autoscale  SLO-driven scaling through the controller ledger: a burst
             against a min_replicas fleet must produce a scale_up record
             within 2 evaluation intervals of burst start, and the
             post-burst scale-down must drain without a single failed
             request (drain_timeout count comes from the same ledger).

Modes:
  --measure   the real measurement child (asserts the acceptance gates)
  --smoke     tier-1 CPU gate: small fixed-count fleet — affinity fleet
              hit rate must beat the p2c baseline, and the autoscale
              rung must scale up, then drain down with zero dropped
              requests
  (no flag)   parent: runs --measure once under a timeout (bench.py's
              run_measure_child)

The fleet replicas are separate CPU worker processes (no `num_tpus`, `tiny`
model), and everything counted here lives in the routing/control plane — so
every mode pins the CPU backend up front and the record says backend=cpu.
Four one-chip replicas at real widths are ROADMAP R5's cell, not this.
"""

import asyncio
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# must land in the env before ANY jax import, ours or a replica child's
os.environ.setdefault("JAX_PLATFORMS", "cpu")

PAGE = 16                       # tiny-preset KV page
REPLICAS = int(os.environ.get("FLEET_REPLICAS", 3))
FAMILIES = int(os.environ.get("FLEET_FAMILIES", 9))
PREFIX_PAGES = int(os.environ.get("FLEET_PREFIX_PAGES", 8))
SLOTS = int(os.environ.get("FLEET_SLOTS", 4))
SECONDS = float(os.environ.get("FLEET_SECONDS", 10))
WARMUP_S = float(os.environ.get("FLEET_WARMUP_S", 6))
RPS = float(os.environ.get("FLEET_RPS", 6))
SLO_TTFT_S = float(os.environ.get("FLEET_SLO_TTFT_S", 0.4))
MAX_TAIL_PAGES = 3
MAX_TOKENS_CAP = 6

# prompt geometry shared by workload + LLMConfig
_PLEN_MAX = (PREFIX_PAGES + MAX_TAIL_PAGES) * PAGE + 3


def _pool_pages(affinity_fair: bool) -> int:
    """Per-replica KV pool: active sequences always fit (SLOTS * pages per
    seq), plus a cache share big enough for ~FAMILIES/REPLICAS families
    (affinity's steady state) but far below FAMILIES families (p2c's)."""
    per_seq = _PLEN_MAX // PAGE + 2
    active = SLOTS * per_seq
    share = -(-FAMILIES // REPLICAS) * (PREFIX_PAGES + 2) + 8
    return active + share + 1  # +1: reserved null page


def _deployment(num_replicas, pool_pages, autoscaling=None):
    from ray_tpu import serve

    @serve.deployment(num_replicas=num_replicas, max_ongoing_requests=16,
                      autoscaling_config=autoscaling)
    class FleetLLM:
        def __init__(self, pool_pages):
            from ray_tpu.serve.llm import LLMConfig, LLMServer
            smax = _PLEN_MAX + MAX_TOKENS_CAP + 2 * PAGE
            self._srv = LLMServer(LLMConfig(
                preset="tiny", max_batch_slots=SLOTS,
                max_seq_len=smax,
                paged=True, page_size=PAGE, prefill_chunk=32,
                prefix_cache=True, seed=0, num_pages=pool_pages,
                # KV widened to LLM-realistic cost (the tiered-bench CPU
                # trick) so a prefix-cache MISS pays a visible prefill —
                # the quantity affinity vs p2c actually trades on
                model_overrides=dict(n_layers=4, n_kv_heads=4, n_heads=4,
                                     head_dim=64, max_seq_len=smax)))

        async def generate(self, prompt, max_tokens=4):
            out = await self._srv.generate(prompt, max_tokens=max_tokens)
            return {"ttft_s": out["ttft_s"], "n": len(out["tokens"])}

        # routing hints + SLO frames ride the replica stats piggyback
        def prefix_digest(self, max_bytes=None):
            return self._srv.prefix_digest(max_bytes)

        def slo_snapshot(self):
            return self._srv.slo_snapshot()

        def cache_stats(self):
            s = self._srv.stats()
            return {k: s.get(k) for k in
                    ("prefix_hit_tokens", "prefix_query_tokens",
                     "prefix_hit_rate", "prefix_cached_pages",
                     "pages_in_use")}

        def trace_phases(self):
            """This replica's engine phases, from the engine's own
            counters: name -> {count, total_s}."""
            d = self._srv.stats()["decode"]
            return {k: {"count": d["phase_n"][k],
                        "total_s": round(d["phase_s"][k], 4)}
                    for k in d["phase_s"]}

    return FleetLLM


# ----------------------------------------------------------------- workload

def _mk_families(n=None, pages=None):
    rng = random.Random(1234)
    return [[rng.randrange(1, 251)
             for _ in range((pages or PREFIX_PAGES) * PAGE)]
            for _ in range(n or FAMILIES)]


def _mk_request(rng, fams):
    """Uniform family popularity, heavy-tailed (lognormal) tail length and
    output length. Token ids stay inside the tiny preset's vocab.

    Popularity is deliberately uniform, not Zipf: a skewed distribution
    lets plain LRU keep the hot families resident on EVERY replica (no
    thrash for p2c to lose to) while funnelling the head family's traffic
    through a single affinity target (queueing, not caching, then
    dominates TTFT). Uniform popularity is the regime prefix routing is
    for — aggregate working set larger than one replica's pool, load
    naturally balanced across the family → replica partition."""
    fam = rng.randrange(len(fams))
    tail_pages = min(int(rng.lognormvariate(0.0, 1.0)), MAX_TAIL_PAGES)
    tail = [rng.randrange(1, 251) for _ in range(tail_pages * PAGE + 3)]
    max_toks = max(2, min(int(rng.lognormvariate(1.2, 0.6)), MAX_TOKENS_CAP))
    return fams[fam] + tail, max_toks


def _arrivals(seconds, rps, rng):
    """Poisson arrival offsets with a diurnal burst: the middle third of
    the window runs at 2x the base rate."""
    t, out = 0.0, []
    while True:
        mult = 2.0 if seconds / 3 <= t < 2 * seconds / 3 else 1.0
        t += rng.expovariate(rps * mult)
        if t >= seconds:
            return out
        out.append(t)


def _drive_open_loop(handle, fams, seconds, rps, seed):
    """Open-loop submit: arrival times are drawn up front and never wait
    on completions (a slow fleet builds a backlog instead of throttling
    the generator). Returns per-request records + the wall clock."""
    rng = random.Random(seed)
    arrivals = _arrivals(seconds, rps, rng)
    reqs = [_mk_request(rng, fams) for _ in arrivals]
    recs = []
    t_start = time.perf_counter()
    for t_arr, (prompt, max_toks) in zip(arrivals, reqs):
        lag = t_arr - (time.perf_counter() - t_start)
        if lag > 0:
            time.sleep(lag)
        e = {"t0": time.perf_counter(), "done": None}
        resp = handle.remote(prompt, max_tokens=max_toks)
        e["resp"] = resp
        try:
            resp._ref.future().add_done_callback(
                lambda f, e=e: e.__setitem__("done", time.perf_counter()))
        except Exception:  # noqa: BLE001 - wall falls back to result time
            pass
        recs.append(e)
    for e in recs:
        try:
            out = e["resp"].result(timeout_s=180)
            e["ttft_s"], e["n"] = out["ttft_s"], out["n"]
        except Exception as ex:  # noqa: BLE001 - counted, never raised
            e["err"] = repr(ex)[:160]
        if e["done"] is None:
            e["done"] = time.perf_counter()
        del e["resp"]
    return recs, time.perf_counter() - t_start


def _pct(sorted_vals, p):
    return sorted_vals[min(int(len(sorted_vals) * p), len(sorted_vals) - 1)]


def _summarize(recs, wall):
    ok = [e for e in recs if "err" not in e]
    ttfts = sorted(e["ttft_s"] for e in ok)
    lats = sorted(e["done"] - e["t0"] for e in ok)
    tpots = sorted((e["done"] - e["t0"] - e["ttft_s"]) /
                   max(e["n"] - 1, 1) * 1e3 for e in ok)
    good = sum(1 for e in ok if e["ttft_s"] <= SLO_TTFT_S)
    return {"requests": len(recs), "failed": len(recs) - len(ok),
            "sustained_rps": round(len(ok) / max(wall, 1e-9), 2),
            "ttft_p50_ms": round(_pct(ttfts, 0.50) * 1e3, 1),
            "ttft_p99_ms": round(_pct(ttfts, 0.99) * 1e3, 1),
            "latency_p99_ms": round(_pct(lats, 0.99) * 1e3, 1),
            "tpot_p99_ms": round(_pct(tpots, 0.99), 2),
            "goodput_rps": round(good / max(wall, 1e-9), 2),
            "slo_ttft_s": SLO_TTFT_S}


# -------------------------------------------------------- fleet inspection

def _replica_call(app, name, method):
    """Fan a zero-arg method out to EVERY replica (a handle routes to one)."""
    import ray_tpu
    from ray_tpu.serve.controller import get_controller
    reps = ray_tpu.get(get_controller().get_replicas.remote(app, name))
    out = []
    for r in reps:
        try:
            out.append(ray_tpu.get(r.handle_request.remote(method),
                                   timeout=30))
        except Exception:  # noqa: BLE001 - replica mid-restart
            pass
    return out


def _fleet_cache_stats(app, name="FleetLLM"):
    stats = _replica_call(app, name, "cache_stats")
    hit = sum(s["prefix_hit_tokens"] for s in stats)
    q = sum(s["prefix_query_tokens"] for s in stats)
    return {"replicas": len(stats), "hit_tokens": hit, "query_tokens": q,
            "hit_rate": round(hit / max(q, 1), 4)}


def _fleet_trace_phases(app, name="FleetLLM"):
    merged = {}
    for frame in _replica_call(app, name, "trace_phases"):
        for k, d in frame.items():
            m = merged.setdefault(k, {"count": 0, "total_s": 0.0})
            m["count"] += d["count"]
            m["total_s"] = round(m["total_s"] + d["total_s"], 4)
    return merged


def _digest_wire_bytes(app, name="FleetLLM"):
    """Packed size of every advertised digest — the <=4 KiB wire bound."""
    import ray_tpu
    from ray_tpu.serve import prefix_digest as pd
    from ray_tpu.serve.controller import get_controller
    state = ray_tpu.get(get_controller().get_replica_state.remote(app, name))
    return {i: pd.digest_nbytes(d)
            for i, d in (state.get("digests") or {}).items()}


# ----------------------------------------------------------------- sections

def _routing_phase(affinity, fams, seconds, rps, label):
    from ray_tpu import serve
    from ray_tpu.util import metrics
    prev = os.environ.get("RAY_TPU_PREFIX_AFFINITY")
    os.environ["RAY_TPU_PREFIX_AFFINITY"] = "1" if affinity else "0"
    app = f"fleet-{label}"
    try:
        dep = _deployment(REPLICAS, _pool_pages(affinity))
        h = serve.run(dep.bind(_pool_pages(affinity)), name=app)
        hg = h.options(method_name="generate")
        # unmeasured warm segment: per-replica jax compiles + cache fill to
        # steady state (fresh app per phase — neither inherits the other's
        # warm caches)
        _drive_open_loop(hg, fams, WARMUP_S, rps * 0.6, seed=7)
        time.sleep(1.2)            # > digest TTL: hints published fleet-wide
        hg._refresh(force=True)
        c0 = _fleet_cache_stats(app)
        f0 = metrics.serve_fleet_counters()
        recs, wall = _drive_open_loop(hg, fams, seconds, rps, seed=11)
        c1 = _fleet_cache_stats(app)
        f1 = metrics.serve_fleet_counters()
        rec = _summarize(recs, wall)
        rec["offered_rps"] = rps
        rec["fleet_hit_rate"] = round(
            (c1["hit_tokens"] - c0["hit_tokens"]) /
            max(c1["query_tokens"] - c0["query_tokens"], 1), 4)
        rec["affinity_counters"] = {
            k: round(f1[k] - f0[k]) for k in
            ("affinity_hits", "affinity_misses", "affinity_spills")}
        rec["digest_wire_bytes"] = _digest_wire_bytes(app)
        rec["trace_phases"] = _fleet_trace_phases(app)
        return rec
    finally:
        serve.delete(app)
        if prev is None:
            os.environ.pop("RAY_TPU_PREFIX_AFFINITY", None)
        else:
            os.environ["RAY_TPU_PREFIX_AFFINITY"] = prev


def bench_routing(seconds=None, rps=None):
    fams = _mk_families()
    seconds = seconds or SECONDS
    rps = rps or RPS
    aff = _routing_phase(True, fams, seconds, rps, "aff")
    p2c = _routing_phase(False, fams, seconds, rps, "p2c")
    rec = {"replicas": REPLICAS, "families": FAMILIES,
           "prefix_pages": PREFIX_PAGES,
           "pool_pages": _pool_pages(True) - 1,
           "affinity": aff, "p2c": p2c,
           "goodput_ratio": round(
               aff["goodput_rps"] / max(p2c["goodput_rps"], 1e-9), 2),
           "ttft_p99_ratio": round(
               aff["ttft_p99_ms"] / max(p2c["ttft_p99_ms"], 1e-9), 3)}
    # ISSUE 20 acceptance gates, asserted inside the committed record
    assert aff["failed"] == 0 and p2c["failed"] == 0, rec
    assert aff["fleet_hit_rate"] > p2c["fleet_hit_rate"], rec
    assert max(d for d in aff["digest_wire_bytes"].values()) <= 4096, rec
    assert (rec["goodput_ratio"] >= 1.5
            or rec["ttft_p99_ratio"] <= 0.6), rec
    return rec


def bench_autoscale(interval_s=1.0, burst_conc=10, burst_s=None,
                    llm_fleet=True):
    """Burst a min_replicas fleet, read the reaction off the controller's
    scale ledger, then let it drain down — the zero-failed-requests gate
    covers the scale-down drain path."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.controller import get_controller
    from ray_tpu.serve.deployment import AutoscalingConfig
    from ray_tpu.util import metrics

    auto = AutoscalingConfig(min_replicas=1, max_replicas=REPLICAS,
                             target_ongoing_requests=2.0,
                             target_ttft_p99_s=SLO_TTFT_S)
    app, name = "fleet-scale", None
    if llm_fleet:
        dep = _deployment(1, _pool_pages(True), autoscaling=auto)
        name = "FleetLLM"
        bound = dep.bind(_pool_pages(True))
        work = ("generate", {"max_tokens": 6})
    else:
        @serve.deployment(num_replicas=1, max_ongoing_requests=16,
                          autoscaling_config=auto)
        class Sleeper:
            async def generate(self, prompt, max_tokens=6):
                await asyncio.sleep(0.25)
                return {"ttft_s": 0.0, "n": max_tokens}
        name = "Sleeper"
        bound = Sleeper.bind()
        work = ("generate", {"max_tokens": 6})

    # the autoscaler loop starts AFTER warmup, so compile stalls during
    # warmup can't register as the "burst" this section measures
    h = serve.run(bound, name=app, _autoscale_interval_s=None)
    hg = h.options(method_name=work[0])
    fams = _mk_families(4)
    prompt = fams[0]
    for _ in range(3):  # warm/compile the single replica
        hg.remote(prompt, **work[1]).result(timeout_s=180)

    ctrl = get_controller()
    ray_tpu.get(ctrl.start_autoscaler.remote(interval_s))
    t_burst = time.time()
    failed, done = 0, 0
    inflight = []
    deadline = time.time() + (burst_s or max(4 * interval_s, 3.0))
    i = 0
    while time.time() < deadline:
        while len(inflight) < burst_conc:
            p, mt = _mk_request(random.Random(100 + i), fams)
            inflight.append(hg.remote(p, max_tokens=mt))
            i += 1
        r = inflight.pop(0)
        try:
            r.result(timeout_s=180)
            done += 1
        except Exception:  # noqa: BLE001
            failed += 1
    # drain phase: a few stragglers keep replicas busy while the ledger's
    # scale_down + drain-before-terminate runs underneath them
    for r in inflight + [hg.remote(prompt, **work[1]) for _ in range(3)]:
        try:
            r.result(timeout_s=180)
            done += 1
        except Exception:  # noqa: BLE001
            failed += 1
    t_down = time.time() + 60
    while time.time() < t_down:
        if ray_tpu.get(ctrl.num_replicas.remote(app, name)) <= 1:
            break
        time.sleep(0.2)
    events = [e for e in ray_tpu.get(ctrl.scale_events.remote(64))
              if e.get("app") == app]
    up = [e for e in events if e["action"] == "scale_up"]
    down = [e for e in events if e["action"] == "scale_down"]
    drains = [e for e in events if e["action"] == "drain_timeout"]
    reaction = round(up[0]["ts"] - t_burst, 3) if up else None
    rec = {"interval_s": interval_s, "requests": done + failed,
           "failed": failed,
           "reaction_s": reaction,
           "reaction_intervals": (round(reaction / interval_s, 2)
                                  if reaction is not None else None),
           "scale_up_reasons": [e.get("reason") for e in up],
           "scale_down_reasons": [e.get("reason") for e in down],
           "drain_timeouts": len(drains),
           "final_replicas": ray_tpu.get(ctrl.num_replicas.remote(app, name)),
           "died_retries": metrics.serve_fleet_counters()["died_retries"]}
    serve.delete(app)
    # ISSUE 20 acceptance gates: reaction within 2 evaluation intervals,
    # scale-down drains with zero failed requests
    assert up and down, rec
    assert rec["reaction_intervals"] <= 2.0, rec
    assert failed == 0, rec
    assert rec["final_replicas"] == 1, rec
    return rec


# ------------------------------------------------------------------- modes

def main():
    from bench import _write_result_artifact
    import ray_tpu
    ray_tpu.init(num_cpus=max(REPLICAS * 2 + 2, 8), ignore_reinit_error=True)
    rec = {"bench": "fleet_bench", "backend": "cpu",
           "replicas": REPLICAS, "offered_rps": RPS, "seconds": SECONDS,
           "slo_ttft_s": SLO_TTFT_S}
    for key, fn in (("routing", bench_routing),
                    ("autoscale", bench_autoscale)):
        try:
            rec[key] = fn()
        except Exception as e:  # noqa: BLE001 - record the failure, continue
            rec[key] = {"error": repr(e)[:400]}
    from ray_tpu import serve
    serve.shutdown()
    rec["artifact"] = _write_result_artifact("fleet_bench", rec)
    print(json.dumps(rec))


def smoke() -> int:
    """Tier-1 CPU gate: fixed-count fleet, both ISSUE 20 smoke gates —
    affinity fleet hit rate beats the p2c baseline, and the autoscale
    rung scales up then drains down with zero dropped requests."""
    global FAMILIES, PREFIX_PAGES, SECONDS, WARMUP_S, RPS
    FAMILIES, PREFIX_PAGES = 6, 4
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.util import metrics
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    fams = _mk_families(FAMILIES, PREFIX_PAGES)

    def phase(affinity, label):
        prev = os.environ.get("RAY_TPU_PREFIX_AFFINITY")
        os.environ["RAY_TPU_PREFIX_AFFINITY"] = "1" if affinity else "0"
        app = f"fleet-smoke-{label}"
        try:
            # generous pool: the smoke gate isolates first-visit misses
            # (p2c warms every family on every replica; affinity once)
            dep = _deployment(REPLICAS, 128)
            h = serve.run(dep.bind(128), name=app)
            hg = h.options(method_name="generate")
            for fam in fams:           # seed: one request per family
                hg.remote(fam + [1, 2, 3], max_tokens=2).result(timeout_s=180)
            time.sleep(1.2)            # > digest TTL
            hg._refresh(force=True)
            c0 = _fleet_cache_stats(app)
            for _ in range(4):         # measured: routed by policy
                for fam in fams:
                    hg.remote(fam + [4, 5, 6],
                              max_tokens=2).result(timeout_s=180)
            c1 = _fleet_cache_stats(app)
            wire = _digest_wire_bytes(app)
            return {"hit_rate": round(
                (c1["hit_tokens"] - c0["hit_tokens"]) /
                max(c1["query_tokens"] - c0["query_tokens"], 1), 4),
                "digest_wire_bytes": wire}
        finally:
            serve.delete(app)
            if prev is None:
                os.environ.pop("RAY_TPU_PREFIX_AFFINITY", None)
            else:
                os.environ["RAY_TPU_PREFIX_AFFINITY"] = prev

    aff = phase(True, "aff")
    p2c = phase(False, "p2c")
    f = metrics.serve_fleet_counters()
    rec = {"smoke": "ok", "affinity": aff, "p2c": p2c,
           "affinity_hits": round(f["affinity_hits"])}
    assert aff["hit_rate"] > p2c["hit_rate"], rec          # smoke gate 1
    assert f["affinity_hits"] > 0, rec
    assert max(aff["digest_wire_bytes"].values()) <= 4096, rec
    # gate 2: scale up under burst, drain down with zero dropped requests
    # (sleeper fleet: the control plane is what this rung proves)
    rec["autoscale"] = bench_autoscale(interval_s=0.25, burst_conc=10,
                                       burst_s=2.0, llm_fleet=False)
    serve.shutdown()
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    if "--smoke" in sys.argv[1:]:
        sys.exit(smoke())
    elif "--measure" in sys.argv[1:]:
        main()
    else:
        from bench import run_measure_child
        sys.exit(run_measure_child(os.path.abspath(__file__)))
