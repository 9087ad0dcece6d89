"""The `engine.stall_share.*` metrics (PR 37) read the engine's own count of
its stalls through `readers/stall_share.py`: against snapshots of a real
`tiny` engine the reader gives None on a program from before the counters,
0.0 for a window without a stall and the share for one with; the three files
are metrics of their cells."""

import asyncio
import time

import pytest

from perfbench import actors, loader
from perfbench.readers import stall_share

CELLS = {"engine.stall_share.batch": "mixtral8x7b-batch",
         "engine.stall_share.longdoc": "keye30b-longdoc-batch",
         "engine.stall_share.agentloop": "solar250b-agentloop-batch"}
WAIT_S = 120.0


def _snapshot(srv) -> dict:
    """As `ServeReplica.snapshot()` takes it: every plain group of stats()."""
    return {"stats": {k: v for k, v in srv.stats().items()
                      if actors.is_plain(v)}}


@pytest.fixture(scope="module")
def runs():
    """Two windows of one `tiny` engine: one as it runs, one in which a read
    of a decode chunk is held until it is a stall by the engine's own test."""
    import numpy as np

    from ray_tpu.serve.llm import LLMConfig, LLMServer
    from ray_tpu.util import tracing
    srv = LLMServer(LLMConfig(
        preset="tiny", max_batch_slots=2, max_seq_len=64, paged=True,
        page_size=8, num_pages=15, prefill_chunk=16, decode_chunk=4, seed=0))
    rng = np.random.default_rng(5)
    read_chunk, held = srv._read_chunk, []

    def held_read_chunk():
        if not held:
            key, t0, _ = srv._phases.open
            held.append(srv._phases.stall_threshold(key))
            while time.perf_counter() - t0 <= 1.5 * held[0]:
                time.sleep(0.01)
        return read_chunk()

    async def windows():
        async def some(n):
            await asyncio.wait_for(asyncio.gather(*[
                srv.generate(rng.integers(1, 250, 30).tolist(), max_tokens=8)
                for _ in range(n)]), WAIT_S)
        await some(3)              # set-up: the counters are not at 0
        marks = [_snapshot(srv)]
        await some(4)
        marks.append(_snapshot(srv))
        floor, tracing.STALL_FLOOR_S = tracing.STALL_FLOOR_S, 0.05
        srv._read_chunk = held_read_chunk
        try:
            await some(4)
        finally:
            tracing.STALL_FLOOR_S = floor
            del srv._read_chunk
        marks.append(_snapshot(srv))
        return marks

    a, b, c = asyncio.run(windows())
    srv._kv_stash.close()
    return {"quiet": {"counters": {"open": a, "close": b}},
            "stalled": {"counters": {"open": b, "close": c}}}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_three_are_metrics_of_their_cells(name):
    spec = loader.layer_metric(name)
    assert spec["reader"] == "stall_share.py" and "args" not in spec
    assert spec["workloads"] == [CELLS[name]]
    assert (spec["layer"], spec["unit"], spec["better"], spec["source"],
            spec["moves"]) == ("serving engine", "%", "lower",
                               "program_counter", "out_tokens_per_s")
    bench = loader.benchmark()
    [entry] = [m for m in loader.metrics_of(bench, "per_layer", CELLS[name])
               if m["name"] == name]
    assert entry == {k: spec[k] for k in entry}
    others = [w["name"] for w in bench["workloads"] if w["name"] != CELLS[name]]
    for cell in others:
        assert name not in {m["name"] for m in loader.metrics_of(
            bench, "per_layer", cell)}


def test_a_window_without_a_stall_reads_zero(runs):
    stats = runs["quiet"]["counters"]["close"]["stats"]
    assert stats["stalls"] == []         # plain data: the snapshot forwards it
    assert stall_share.read(runs["quiet"], {}) == 0.0


def test_a_window_with_a_stall_reads_its_share(runs):
    before, after = (runs["stalled"]["counters"][k]["stats"]
                     for k in ("open", "close"))
    share = stall_share.read(runs["stalled"], {})
    [record] = after["stalls"]
    assert record["phase"] == "decode_sync" and record["dur_s"] > 0.05
    loop_s = after["decode"]["loop_s"] - before["decode"]["loop_s"]
    assert share == pytest.approx(100.0 * record["dur_s"] / loop_s, rel=1e-3)
    assert 0.0 < share < 100.0


def test_a_program_from_before_the_counters_reads_none(runs):
    """The driver lays this PR's benchmark files over the parent's program:
    its `stats()["decode"]` has no `stall_s`, and nothing may raise."""
    old = {edge: {"stats": dict(snap["stats"], decode={
        k: v for k, v in snap["stats"]["decode"].items()
        if not k.startswith("stall_")})}
        for edge, snap in runs["stalled"]["counters"].items()}
    assert "loop_s" in old["close"]["stats"]["decode"]
    assert stall_share.read({"counters": old}, {}) is None


def test_a_window_in_which_the_loop_did_not_run_reads_none(runs):
    snap = runs["quiet"]["counters"]["close"]
    assert stall_share.read({"counters": {"open": snap, "close": snap}},
                            {}) is None
