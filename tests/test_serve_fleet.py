"""Serve fleet routing + SLO autoscaling (ISSUE 20): prefix-affinity
digest accounting (bounded, stable under demotion, deterministic scoring),
spill-to-p2c fallback, the RAY_TPU_PREFIX_AFFINITY=0 hatch, multiplex pin
rebalancing, ActorDiedError re-route onto a survivor, and the pure
SLO-overlay scale decision."""

import os
import signal
import time

import pytest

from ray_tpu import serve
from ray_tpu.serve import prefix_digest as pd
from ray_tpu.serve.controller import (aggregate_slo, decide_num_replicas_slo)
from ray_tpu.serve.deployment import AutoscalingConfig
from ray_tpu.serve.handle import DeploymentHandle
from ray_tpu.serve.multiplex import should_rebalance_pin
from ray_tpu.serve.radix_cache import PageManager

PS = 4  # tokens per page


def _mgr(num_pages=64, slots=16, max_seq=16, **hooks):
    return PageManager(num_pages, PS, slots, max_seq, True, **hooks)


def _prompt(*pages, tail=1):
    toks = []
    for p in pages:
        toks.extend(range(p * 100, p * 100 + PS))
    toks.extend(range(9000, 9000 + tail))
    return toks


def _publish(m, slot, toks):
    m.allocate_prefix(slot, toks, len(toks))
    m.register_prefix(slot, toks)
    m.free(slot)


# ------------------------------------------------------------- digest units

def test_digest_bounded_and_packed():
    """Digest stays <= max_bytes packed; pack() and digest_nbytes agree;
    truncation keeps the kept set prefix-closed so consecutive-match
    scoring never breaks at an artificial hole."""
    m = _mgr(num_pages=256, slots=64, max_seq=64)
    prompts = []
    for fam in range(16):
        toks = _prompt(fam * 4 + 1, fam * 4 + 2, fam * 4 + 3)
        prompts.append(toks)
        _publish(m, fam % 8, toks)
    # heat a few families so truncation has a real ranking to apply
    for _ in range(5):
        _publish(m, 0, prompts[0])
        _publish(m, 1, prompts[1])

    small = m.prefix_digest(max_bytes=256)
    assert pd.digest_nbytes(small) <= 256
    assert len(pd.pack(small)) == pd.digest_nbytes(small)
    full = m.prefix_digest(max_bytes=4096)
    assert pd.digest_nbytes(full) <= 4096
    assert len(full["entries"]) > len(small["entries"])
    for dg in (small, full):
        for toks in prompts:
            hashes = pd.prompt_chain_hashes(toks, PS)
            present = sum(1 for h in hashes if h in dg["entries"])
            assert pd.match_depth(dg, hashes) == present  # prefix-closed
    # the hottest family survived the aggressive truncation
    assert pd.match_depth(small, pd.prompt_chain_hashes(prompts[0], PS)) > 0


def test_digest_stable_under_demotion():
    """LRU-demoted (restorable) chains keep advertising in the digest —
    the router can still route to them and the replica restores from the
    stash instead of re-prefilling. Without a demotion plane the evicted
    entry drops (it really is a miss)."""
    stash = {}
    seq = iter(range(10 ** 6))

    def demote(pid, node):
        h = next(seq)
        stash[h] = True
        return h

    def restore(h, pid):
        return h in stash

    m = _mgr(num_pages=8, demote_cb=demote, restore_cb=restore)
    a = _prompt(1, 2)
    _publish(m, 0, a)
    before = m.prefix_digest()
    hashes = pd.prompt_chain_hashes(a, PS)
    assert pd.match_depth(before, hashes) == 2

    # drain the pool: published pages demote to the stash
    big = _prompt(8, 9, 10, tail=4 * PS)
    m.allocate_prefix(1, big, 7 * PS)
    assert m.demoted_pages >= 2
    m.free(1)
    after = m.prefix_digest()
    assert pd.match_depth(after, hashes) == 2      # stable under demotion

    # no demotion plane: eviction is a real discard -> digest drops it
    m2 = _mgr(num_pages=8)
    _publish(m2, 0, a)
    m2.allocate_prefix(1, big, 7 * PS)
    m2.free(1)
    assert pd.match_depth(m2.prefix_digest(), hashes) < 2


def test_digest_deterministic():
    m = _mgr()
    _publish(m, 0, _prompt(1, 2))
    _publish(m, 1, _prompt(1, 7))
    assert m.prefix_digest() == m.prefix_digest()


# ------------------------------------------------------------ router scoring

def _fake_handle(n_replicas, digests):
    h = DeploymentHandle("d")
    h._replicas = [f"r{i}" for i in range(n_replicas)]
    h._inflight = {i: 0 for i in range(n_replicas)}
    h._digests = digests
    return h


def _family_digest(tokens, hits=10):
    hashes = pd.prompt_chain_hashes(tokens, PS)
    return pd.build([(h, hits, i + 1) for i, h in enumerate(hashes)], PS)


def test_router_scoring_deterministic_and_affine():
    fam_a, fam_b = _prompt(1, 2, 3), _prompt(5, 6, 7)
    h = _fake_handle(3, {0: _family_digest(fam_a), 2: _family_digest(fam_b)})
    for _ in range(20):
        assert h._pick_replica(fam_a) == 0
        assert h._pick_replica(fam_b) == 2
    # deeper match beats shallower: replica 1 holds only fam_a's first page
    partial = _family_digest(_prompt(1, tail=0))
    h2 = _fake_handle(3, {0: _family_digest(fam_a), 1: partial})
    assert all(h2._pick_replica(fam_a) == 0 for _ in range(10))


def test_router_spills_hot_replica_to_p2c():
    fam_a = _prompt(1, 2, 3)
    h = _fake_handle(3, {0: _family_digest(fam_a)})
    assert h._pick_by_prefix(fam_a) == 0
    # affinity target's queue is spill_threshold deeper than the idlest
    h._inflight = {0: pd.spill_threshold() + 1, 1: 0, 2: 0}
    assert h._pick_by_prefix(fam_a) is None        # spilled back to p2c
    picks = {h._pick_replica(fam_a) for _ in range(40)}
    assert picks - {0}                             # p2c reaches survivors


def test_router_no_match_and_escape_hatch(monkeypatch):
    fam_a, other = _prompt(1, 2, 3), _prompt(11, 12, 13)
    h = _fake_handle(2, {0: _family_digest(fam_a)})
    assert h._pick_by_prefix(other) is None        # no digest holds it
    monkeypatch.setenv("RAY_TPU_PREFIX_AFFINITY", "0")
    h._inflight = {0: 5, 1: 0}

    def boom(_tokens):
        raise AssertionError("affinity consulted with the hatch closed")

    h._pick_by_prefix = boom
    assert h._pick_replica(fam_a) in (0, 1)        # pure p2c, no scoring


# --------------------------------------------------------- multiplex rebalance

def test_should_rebalance_pin_math():
    assert should_rebalance_pin([10, 1], 0)        # 2-replica skew works
    assert not should_rebalance_pin([3, 3], 0)     # balanced fleet holds
    assert not should_rebalance_pin([1, 0], 0)     # under min_inflight
    assert not should_rebalance_pin([5], 0)        # single replica
    assert should_rebalance_pin([9, 2, 1], 0)      # 9 > 2 * median_low(2)
    assert not should_rebalance_pin([4, 2, 3], 0)  # 4 <= 2 * 2


# ------------------------------------------------------------- SLO decisions

def _auto(**kw):
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 8)
    kw.setdefault("target_ongoing_requests", 2.0)
    return AutoscalingConfig(**kw)


def test_decide_slo_breach_forces_upscale():
    auto = _auto(target_ttft_p99_s=0.5)
    n, why = decide_num_replicas_slo(2, 2, auto, {"ttft_p99_s": 1.2})
    assert (n, why) == (3, "slo_breach")
    # clamped at max even under breach
    n, _ = decide_num_replicas_slo(2, 8, auto, {"ttft_p99_s": 1.2})
    assert n == 8
    # TPOT breach counts too
    auto2 = _auto(target_tpot_p99_ms=20.0)
    n, why = decide_num_replicas_slo(0, 2, auto2, {"tpot_p99_ms": 80.0})
    assert (n, why) == (3, "slo_breach")


def test_decide_occupancy_forces_upscale():
    auto = _auto()
    n, why = decide_num_replicas_slo(2, 2, auto, {"occupancy_mean": 0.95})
    assert (n, why) == (3, "occupancy")


def test_decide_slo_holds_downscale_until_margin():
    auto = _auto(target_ttft_p99_s=1.0)
    # ongoing-count says shrink, but p99 is near target: hold
    n, why = decide_num_replicas_slo(1, 4, auto, {"ttft_p99_s": 0.9})
    assert (n, why) == (4, "slo_hold")
    # comfortably inside margin: the shrink goes through
    n, why = decide_num_replicas_slo(1, 4, auto, {"ttft_p99_s": 0.2})
    assert (n, why) == (1, "ongoing")
    # no snapshot at all: plain ongoing policy
    n, why = decide_num_replicas_slo(1, 4, auto, None)
    assert (n, why) == (1, "ongoing")


def test_aggregate_slo_worst_case():
    frames = [{"ttft_p99_s": 0.1, "tpot_p99_ms": 5.0, "occupancy_mean": 0.2},
              {"ttft_p99_s": 0.9, "tpot_p99_ms": None, "occupancy_mean": 0.6},
              None]
    agg = aggregate_slo(frames)
    assert agg["ttft_p99_s"] == 0.9                # one hot replica counts
    assert agg["tpot_p99_ms"] == 5.0
    assert abs(agg["occupancy_mean"] - 0.4) < 1e-9
    assert aggregate_slo([]) is None and aggregate_slo([None]) is None


def test_histogram_window_is_delta():
    from ray_tpu.util import metrics
    name = "test_fleet_window_hist"
    hist = metrics.get_or_create(metrics.Histogram, name, "t",
                                 boundaries=[1, 10, 100])
    state = {}
    hist.observe(5)
    hist.observe(5)
    w = metrics.histogram_window(name, state)
    assert w["count"] == 2
    assert metrics.histogram_window(name, state) is None   # nothing new
    hist.observe(50)
    w = metrics.histogram_window(name, state)
    assert w["count"] == 1 and w["p50"] > 10               # only the delta


# ------------------------------------------------------------------- cluster

@pytest.fixture(scope="module")
def serve_session():
    import ray_tpu
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    serve.shutdown()


# page size the canned-digest deployment advertises (any int works; the
# router recomputes prompt hashes per advertised page size)
_ADV_PS = 8
_FAMS = [list(range(0, 4 * _ADV_PS)), list(range(500, 500 + 4 * _ADV_PS))]


def test_digest_piggyback_routes_to_advertiser(serve_session):
    """End-to-end affinity: each replica advertises one prompt family via
    the stats piggyback (replica -> controller cache -> handle refresh),
    and requests carrying family tokens land on the advertising replica —
    no per-request controller chatter."""
    @serve.deployment(num_replicas=2)
    class Advertiser:
        def __init__(self):
            tag = serve.get_replica_context().replica_tag
            self._idx = int(tag.rsplit("#", 1)[1]) % 2

        def prefix_digest(self):
            hashes = pd.prompt_chain_hashes(_FAMS[self._idx], _ADV_PS)
            return pd.build([(h, 10, i + 1) for i, h in enumerate(hashes)],
                            _ADV_PS)

        def which(self, tokens):
            return self._idx

    h = serve.run(Advertiser.bind(), name="adv")
    hw = h.options(method_name="which")
    hw._refresh(force=True)
    assert hw._digests, "digests should piggyback on the refresh"
    for fam_idx in (0, 1):
        got = {hw.remote(list(_FAMS[fam_idx])).result(timeout_s=60)
               for _ in range(6)}
        assert got == {fam_idx}
    serve.delete("adv")


def test_mux_pin_rebalances_off_hot_replica(serve_session):
    """Skewed model traffic: a pin whose replica is 2x over the fleet
    median inflight is evicted and re-pinned on the idler replica."""
    from ray_tpu.util import metrics

    @serve.deployment(num_replicas=2)
    class Mux:
        def echo(self, x):
            return x

    h = serve.run(Mux.bind(), name="mux-reb")
    mh = h.options(method_name="echo", multiplexed_model_id="lora-A")
    mh._refresh(force=True)
    before = metrics.serve_fleet_counters()["mux_rebalances"]
    with mh._lock:
        mh._model_affinity["lora-A"] = 0
        mh._inflight = {0: 10, 1: 1}        # replica 0 is drowning
    assert mh.remote(7).result(timeout_s=60) == 7
    assert mh._model_affinity["lora-A"] == 1
    assert metrics.serve_fleet_counters()["mux_rebalances"] == before + 1
    serve.delete("mux-reb")


def test_replica_death_reroutes_to_survivor(serve_session):
    """Chaos kill: SIGKILL one replica's worker process mid-traffic. A
    request routed into the corpse force-refreshes the replica set and
    retries on the survivor instead of erroring (ISSUE 20 satellite)."""
    import ray_tpu
    from ray_tpu.serve.controller import get_controller
    from ray_tpu.util import metrics

    @serve.deployment(num_replicas=2)
    class Victim:
        def echo(self, x):
            return x * 2

    h = serve.run(Victim.bind(), name="death")
    ctrl = get_controller()
    reps = ray_tpu.get(ctrl.get_replicas.remote("death", "Victim"))
    pids = [ray_tpu.get(r.stats.remote(), timeout=30)["pid"] for r in reps]
    assert pids[0] != pids[1]

    he = h.options(method_name="echo")
    he._refresh(force=True)
    dead_id = getattr(reps[0], "_actor_id", None)
    dead_idx = next(i for i, r in enumerate(he._replicas)
                    if getattr(r, "_actor_id", None) == dead_id)

    os.kill(pids[0], signal.SIGKILL)
    time.sleep(0.2)
    before = metrics.serve_fleet_counters()["died_retries"]
    # pin the multiplex path straight into the corpse: without the retry
    # this request errors with ActorDiedError
    hm = h.options(method_name="echo", multiplexed_model_id="m0")
    hm._refresh(force=True)
    with hm._lock:
        hm._model_affinity["m0"] = dead_idx
    assert hm.remote(21).result(timeout_s=60) == 42
    assert metrics.serve_fleet_counters()["died_retries"] >= before + 1
    # the corpse's pin was evicted; follow-ups route clean
    assert hm._model_affinity.get("m0") != dead_idx
    for _ in range(5):
        assert he.remote(1).result(timeout_s=60) == 2
    serve.delete("death")


def test_fleet_bench_smoke_gate():
    """Tier-1 hook for the fleet bench's --smoke mode: a 3-replica CPU
    fleet must show a higher fleet prefix-cache hit rate under affinity
    routing than under the p2c baseline, keep every digest within the
    4 KiB wire bound, and the autoscale rung must scale up within two
    evaluation intervals then drain down with zero dropped requests."""
    import json
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks",
                                      "fleet_bench.py"), "--smoke"],
        capture_output=True, text=True, timeout=420, env=env)
    assert p.returncode == 0, (p.stdout, p.stderr)
    rec = json.loads(p.stdout.strip().splitlines()[-1])
    assert rec["smoke"] == "ok"
    assert rec["affinity"]["hit_rate"] > rec["p2c"]["hit_rate"]
    assert max(rec["affinity"]["digest_wire_bytes"].values()) <= 4096
    auto = rec["autoscale"]
    assert auto["failed"] == 0
    assert auto["reaction_intervals"] <= 2.0
    assert auto["final_replicas"] == 1
