"""`generators/mixed_backlog.py` on the cell's own parameters
(`traffic/mixedlen.json`): which places are long, that every seed has the
same set of lengths of each kind, that ids stay inside the vocabulary slice,
and that the list never drains."""

from collections import Counter

from perfbench import loader
from perfbench.generators import mixed_backlog

MODEL = {"vocab": 32768}
TRAFFIC = loader.traffic_of("mixedlen")
SECONDS = 45.0


def _plan(seed, traffic=TRAFFIC):
    return mixed_backlog.plan(traffic, seed, SECONDS, MODEL)


def test_places_0_and_4_of_every_block_of_8_are_long():
    plan = _plan(3)
    short, long_ = TRAFFIC["short_prompt"], TRAFFIC["long_prompt"]
    for i, r in enumerate(plan["requests"]):
        n = len(r["prompt"])
        if i % 8 in (0, 4):
            assert long_["min"] <= n <= long_["max"], (i, n)
        else:
            assert short["min"] <= n <= short["max"], (i, n)
        assert 32 <= r["max_tokens"] <= 1024
        assert r["kind"] == ("ramp" if i < 48 else
                             "long" if i % 8 in (0, 4) else "short")
    # short prompts lie wholly inside the window, long ones are 2 to 12 of it
    assert short["max"] <= 4096 // 2 and long_["min"] >= 2 * 4096
    # by prompt tokens the mix is nearly all long
    tokens = Counter()
    for i, r in enumerate(plan["requests"]):
        tokens[i % 8 in (0, 4)] += len(r["prompt"])
    assert tokens[True] / sum(tokens.values()) > 0.9


def test_ramp_answers_are_staggered_and_the_window_opens_with_long_rows():
    plan = _plan(5)
    ramp = plan["requests"][:plan["ramp"]]
    outs = [r["max_tokens"] for r in ramp]
    assert plan["ramp"] == 48 and outs == sorted(outs)
    assert outs[0] == 32 and outs[-1] == 384
    assert sum(len(r["prompt"]) >= 8192 for r in ramp) == 12
    assert plan["mode"] == "closed" and plan["in_flight"] == 72
    assert plan["setup"] == []            # set-up is the harness's pool fill
    assert plan["check_prompt_lens"] == [1536, 6144, 20480, 1536, 6144, 20480]
    assert plan["warm"] == {"prompt_min": 64, "prompt_max": 49152,
                            "sharing": False}


def test_every_seed_has_the_same_set_of_lengths_of_each_kind():
    def sets(seed):
        reqs = _plan(seed)["requests"]
        return (sorted(len(r["prompt"]) for i, r in enumerate(reqs) if i % 8 in (0, 4)),
                sorted(len(r["prompt"]) for i, r in enumerate(reqs) if i % 8 not in (0, 4)),
                sorted(r["max_tokens"] for r in reqs))
    a, b = sets(1), sets(2 ** 31 + 77)
    assert a == b
    order = lambda seed: [len(r["prompt"]) for r in _plan(seed)["requests"]]
    assert order(1) != order(2 ** 31 + 77)      # the seed swaps neighbours
    assert order(9) == order(9)
    # a seed moves a length by one place of its kind at most
    longs = lambda seed: [n for i, n in enumerate(order(seed)) if i % 8 in (0, 4)]
    x, y = longs(1), longs(2)
    assert all(x[i] in y[max(0, i - 1):i + 2] for i in range(len(x)))


def test_ids_are_drawn_apart_from_the_slice():
    plan = _plan(2 ** 31 + 5)
    firsts = set()
    for r in plan["requests"]:
        assert 0 <= min(r["prompt"]) and max(r["prompt"]) < MODEL["vocab"]
        firsts.add(tuple(r["prompt"][:64]))
    # nothing shared: no two prompts open with the same page
    assert len(firsts) == len(plan["requests"])


def test_the_list_never_drains_at_6_a_second():
    plan = _plan(0)
    assert len(plan["requests"]) == 48 + 6 * 45 + 72
    # what a window could complete at the builder's arithmetic (a block of 8
    # in 3 s) is well under what the list holds
    assert len(plan["requests"]) - 48 > 2 * (SECONDS / 3.0) * 8
    assert max(len(r["prompt"]) + r["max_tokens"]
               for r in plan["requests"]) <= 51200


def test_rehearsal_group_has_a_window_of_a_few_pages():
    small = {**TRAFFIC, **TRAFFIC["rehearsal"]}
    plan = mixed_backlog.plan(small, 1, 6.0, {"vocab": 256})
    lens = [len(r["prompt"]) for r in plan["requests"]]
    assert max(lens) <= 120 and min(lens) >= 4
    assert all((len(r["prompt"]) >= 32) == (i % 4 == 0)
               for i, r in enumerate(plan["requests"]))


def test_every_32_requests_carry_the_same_prompts():
    """One whole set of strata of each kind every `blocks_per_stratum` blocks:
    the long prompts are nearly all of a window's work, so a stretch of the
    list must not be heavier than the next (six runs spread by 0.081 while
    it could be: PERF.md, PR 39)."""
    assert TRAFFIC["blocks_per_stratum"] == 4
    for seed in (0, 2 ** 31 + 9):
        lens = [len(r["prompt"]) for r in _plan(seed)["requests"]]
        sums = {sum(lens[i:i + 32]) for i in range(0, len(lens) - 31, 32)}
        assert len(sums) == 1, sums
        longs = [n for i, n in enumerate(lens) if i % 8 in (0, 4)]
        assert len(set(longs)) == 8 and len(set(longs[:8])) == 8
