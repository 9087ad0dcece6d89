"""The generators give the same requests for the same seed, different ones for
another, and every seed the same SET of sizes and gaps."""

import pytest

from perfbench import loader, sampling

MODEL = {"vocab": 32000}
MIXES = ["chat", "batch", "docqa"]
BIG_SEED = 2**31 + 12345       # more than 32 signed bits hold


def _plan(mix, seed, seconds=45.0, rehearse=False):
    traffic = loader.traffic_of(mix)
    if rehearse:
        traffic = {**traffic, **traffic["rehearsal"]}
    return loader.module("generators", traffic["generator"]).plan(
        traffic, seed, seconds, MODEL)


def _shape(plan):
    return [(r["rid"], r["due_s"], len(r["prompt"]), r["max_tokens"], r["kind"])
            for r in plan["setup"] + plan["requests"]]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    a, b = _plan(mix, BIG_SEED), _plan(mix, BIG_SEED)
    assert _shape(a) == _shape(b)
    assert [r["prompt"] for r in a["requests"]] == [r["prompt"] for r in b["requests"]]


@pytest.mark.parametrize("mix", MIXES)
def test_another_seed_other_requests_same_sizes(mix):
    a, b = _plan(mix, 1), _plan(mix, BIG_SEED)
    assert [r["prompt"][:8] for r in a["requests"][:20]] != \
        [r["prompt"][:8] for r in b["requests"][:20]]
    assert _shape(a) != _shape(b)
    # whole blocks hold the same multiset of output lengths whatever the seed
    n = (min(len(a["requests"]), len(b["requests"])) // 32) * 32
    skip = a.get("ramp", 0)
    outs = lambda p: sorted(r["max_tokens"] for r in p["requests"][skip:skip + n - 32])
    assert n >= 64 and outs(a) == outs(b)


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_stay_inside_the_mix_and_the_row(mix):
    traffic = loader.traffic_of(mix)
    plan = _plan(mix, 7)
    longest = max(len(r["prompt"]) + r["max_tokens"] for r in plan["requests"])
    assert longest <= 8192
    lo = traffic.get("prompt", traffic.get("document"))["min"]
    assert min(len(r["prompt"]) for r in plan["requests"]) >= lo
    assert all(0 <= t < 32000 for r in plan["requests"][:5] for t in r["prompt"])


def test_open_loop_arrivals_rise_in_the_burst_and_keep_their_rate():
    rng = sampling.rng_of(3, 1)
    due = sampling.arrivals(60.0, 4.0, {"from": 1 / 3, "to": 2 / 3, "factor": 2.0}, rng)
    thirds = [sum(lo <= t < lo + 20 for t in due) for lo in (0, 20, 40)]
    assert due == sorted(due) and due[-1] < 60.0
    assert thirds[1] > 1.6 * thirds[0] and thirds[1] > 1.6 * thirds[2]
    assert abs(len(due) - 4.0 * 60 * 4 / 3) < 0.1 * 320


def test_stratified_lengths_have_the_median_and_the_clip():
    spec = {"median": 192, "sigma": 0.9, "min": 16, "max": 1536}
    xs = sampling.lognormal_ints(320, spec, sampling.rng_of(5, 2))
    assert 150 < sorted(xs)[160] < 240 and min(xs) >= 16 and max(xs) <= 1536
    ys = sampling.lognormal_ints(320, spec, sampling.rng_of(6, 2))
    assert xs != ys and sorted(xs) == sorted(ys)


def test_sessions_hits_follow_a_document_that_has_settled():
    plan = _plan("docqa", 11, seconds=40.0)
    traffic = loader.traffic_of("docqa")
    kinds = [r["kind"] for r in plan["requests"]]
    assert len(plan["setup"]) == traffic["recent"]
    share = kinds.count("miss") / len(kinds)
    assert 0.2 <= share <= 0.3
    opened = {}
    for i, r in enumerate(plan["requests"]):
        if r["kind"] == "miss":
            opened[r["doc"]] = i
        else:   # a hit's prompt starts with its document, opened long enough ago
            assert i - opened.get(r["doc"], -10**9) >= traffic["settle_requests"]
    hits = [r for r in plan["requests"] if r["kind"] == "hit"]
    docs = {r["doc"]: r["prompt"] for r in plan["requests"] if r["kind"] == "miss"}
    shared = [r for r in hits if r["doc"] in docs]
    assert shared and all(r["prompt"][:2048] == docs[r["doc"]][:2048] for r in shared)


def test_backlog_never_drains_and_ramps_first():
    plan = _plan("batch", 5, seconds=45.0)
    ramp = plan["requests"][:plan["ramp"]]
    assert [r["kind"] for r in ramp] == ["ramp"] * 32
    assert [r["max_tokens"] for r in ramp] == sorted(r["max_tokens"] for r in ramp)
    assert len(plan["requests"]) >= 45 * 8


def test_the_seed_swaps_neighbours_only():
    """The seed moves a request by fewer than `sampling.SHUFFLE_GROUP` places:
    each run of 2 consecutive requests holds the same lengths and gaps for
    every seed."""
    a, b = _plan("chat", 3), _plan("chat", BIG_SEED)
    n = (min(len(a["requests"]), len(b["requests"])) // 2) * 2 - 2
    for start in range(0, n, 2):
        lens = lambda p: sorted(len(r["prompt"]) for r in p["requests"][start:start + 2])
        assert lens(a) == lens(b)
    dues = lambda p: [r["due_s"] for r in p["requests"][:n:2]]
    assert max(abs(x - y) for x, y in zip(dues(a)[1:], dues(b)[1:])) < 3.0
    assert [len(r["prompt"]) for r in a["requests"]] != [len(r["prompt"]) for r in b["requests"]]
