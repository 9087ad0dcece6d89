"""`generators/growing_sessions.py` on the parameters of `traffic/agentloop.json`."""

import pytest

from perfbench import loader
from perfbench.generators import growing_sessions

MODEL = {"vocab": 24576}


@pytest.fixture(scope="module")
def traffic():
    return loader.traffic_of("agentloop")


@pytest.fixture(scope="module")
def plan(traffic):
    return growing_sessions.plan(traffic, 2147483659, 45.0, MODEL)


def test_the_plan_is_the_cells(traffic, plan):
    assert plan["mode"] == "closed" and plan["in_flight"] == 24
    assert plan["ramp"] == 16
    assert len(plan["requests"]) == 16 + 24 * 45 + 24
    assert len(plan["setup"]) == 40
    lens = [len(r["prompt"]) for r in plan["setup"]]
    assert lens[0] == 8192 and lens[-1] == 32768 and lens == sorted(lens)
    assert all(r["max_tokens"] == 1 for r in plan["setup"])
    outs = [r["max_tokens"] for r in plan["requests"][:16]]
    assert outs[0] == 32 and outs[-1] == 256 and outs == sorted(outs)
    assert all(32 <= r["max_tokens"] <= 384 for r in plan["requests"][16:])
    assert plan["warm"]["sharing"] and plan["warm"]["prompt_max"] == 32768 + 4096
    assert all(0 <= t < 24576 for t in plan["requests"][5]["prompt"][:256])


def test_a_turn_extends_the_sessions_last_prompt(plan):
    last = {j: r["prompt"] for j, r in enumerate(plan["setup"])}
    turns = opens = 0
    for i, r in enumerate(plan["requests"]):
        s = r["session"]
        assert s == i % 40            # round robin: 40 requests between turns
        before = last[s]
        if r["kind"] == "open":
            opens += 1
            assert len(before) > 32768                     # it had retired
            assert 6144 <= len(r["prompt"]) <= 12288
            assert r["prompt"][:64] != before[:64]
        else:
            turns += 1
            assert len(before) <= 32768
            grown = len(r["prompt"]) - len(before)
            assert 512 <= grown <= 4096
            assert r["prompt"][:len(before)] == before      # a prefix hit
        last[s] = r["prompt"]
        assert len(r["prompt"]) + r["max_tokens"] <= 40960
    assert opens >= 40 and turns > 10 * opens
    assert all(r["kind"] == "ramp" for r in plan["requests"][:16])


def test_every_seed_offers_the_same_set_of_lengths(traffic, plan):
    other = growing_sessions.plan(traffic, 3000000019, 45.0, MODEL)

    def grown(p):
        last, out = {j: len(r["prompt"]) for j, r in enumerate(p["setup"])}, []
        for r in p["requests"]:
            if r["kind"] != "open":
                out.append(len(r["prompt"]) - last[r["session"]])
            last[r["session"]] = len(r["prompt"])
        return out

    for key in (lambda p: [r["max_tokens"] for r in p["requests"]], grown):
        a, b = key(plan), key(other)
        assert a != b                       # another order
        # the same set over the window's stretch of whole blocks
        n = len(a) // 32 * 32 if len(a) == len(b) else min(len(a), len(b)) // 64 * 32
        assert sorted(a[:n]) == sorted(b[:n]) or abs(sum(a[:n]) - sum(b[:n])) < 0.01 * sum(a[:n])
    assert plan["requests"][40]["prompt"] != other["requests"][40]["prompt"]


def test_the_rehearsal_mix_fits_its_engine(traffic):
    config = loader.read_json(loader.os.path.join(
        loader.HERE, "configs", "solar-open2-250b-serve.json"))
    small = growing_sessions.plan({**traffic, **traffic["rehearsal"]}, 7, 6.0,
                                  {"vocab": 256})
    longest = max(len(r["prompt"]) + r["max_tokens"]
                  for r in small["requests"] + small["setup"])
    assert longest <= config["rehearsal"]["engine"]["max_seq_len"]
    assert {r["kind"] for r in small["requests"]} == {"ramp", "turn", "open"}
