"""The `wall.*` / `work.*` metrics (PR 25) read the engine loop's own phase
counters through `readers/phase_ratio.py`: each resolves against snapshots of
a real `tiny` engine, is left out on a program from before the counters, and
reaches the line of a rehearsal."""

import asyncio
import glob
import json
import os
import subprocess
import sys

import pytest

from perfbench import loader
from perfbench.readers import phase_ratio

ROOT = loader.ROOT
CELL = "mixtral8x7b-batch"
KEEP = ("active", "free_slots", "requests", "decode", "pages_in_use",
        "pages_free", "prefix_cached_pages", "prefix_hit_tokens",
        "prefix_query_tokens")     # what `ServeReplica.snapshot()` forwards


def _specs():
    return [loader.read_json(p) for p in sorted(glob.glob(os.path.join(
        loader.HERE, "layer_metrics", "w*.json")))]


def _snapshot(srv) -> dict:
    st = srv.stats()
    return {"stats": {k: st[k] for k in KEEP if k in st}}


@pytest.fixture(scope="module")
def run():
    """Counter snapshots round a window of a `tiny` engine that has more
    requests than slots and a pool small enough to evict and demote."""
    import numpy as np

    from ray_tpu.serve.llm import LLMConfig, LLMServer
    srv = LLMServer(LLMConfig(
        preset="tiny", max_batch_slots=2, max_seq_len=64, paged=True,
        page_size=8, num_pages=15, prefill_chunk=16, decode_chunk=4, seed=0))
    rng = np.random.default_rng(5)

    async def window():
        async def some(n):
            await asyncio.wait_for(asyncio.gather(*[
                srv.generate(rng.integers(1, 250, 30).tolist(), max_tokens=8)
                for _ in range(n)]), 120.0)
        await some(3)              # set-up: the counters are not at 0
        before = _snapshot(srv)
        await some(6)
        return before, _snapshot(srv)

    before, after = asyncio.run(window())
    srv._kv_stash.close()
    return {"counters": {"open": before, "close": after}}


def test_there_are_seven_and_they_sort_after_the_rest():
    names = [s["name"] for s in _specs()]
    assert len(names) == 7 and all(
        n.startswith(("wall.", "work.")) for n in names)
    files = sorted(os.listdir(os.path.join(loader.HERE, "layer_metrics")))
    assert files[-7:] == [n + ".json" for n in names]
    bench = loader.benchmark()
    assert [m["name"] for m in bench["per_layer"]][-7:] == names
    assert set(names) <= {m["name"] for m in loader.metrics_of(
        bench, "per_layer", CELL)}


@pytest.mark.parametrize("spec", _specs(), ids=lambda s: s["name"])
def test_each_metric_resolves_against_a_real_engine(run, spec):
    assert spec["reader"] == "phase_ratio.py"
    assert spec["layer"] == "serving engine" and spec["workloads"] == [CELL]
    value = loader.module("readers", spec["reader"]).read(run, spec["args"])
    assert value is not None and value > 0
    # a phase of the loop's own iteration is a share of it; an admission also
    # runs while the loop is idle (here: the window's first two requests find
    # it stopped), which a saturated cell's loop never is
    if spec["args"]["num"].rsplit(".", 1)[-1] in (
            "decode_sync", "prefill_first_token", "yield"):
        assert value < 100


def test_shares_nest_as_the_phases_do(run):
    by_name = {s["name"]: phase_ratio.read(run, s["args"]) for s in _specs()}
    assert (by_name["wall.demote_stash_share.batch"]
            <= by_name["wall.demote_share.batch"]
            <= by_name["wall.admit_share.batch"])
    assert (by_name["wall.decode_sync_share.batch"]
            + by_name["wall.first_token_share.batch"]
            + by_name["wall.yield_share.batch"]) <= 100.0


def test_a_program_from_before_the_counters_reads_none(run):
    """The driver lays this PR's benchmark files over the parent's program:
    its `stats()["decode"]` has no `loop_s`, and nothing may raise."""
    old = {edge: {"stats": dict(snap["stats"], decode={
        k: snap["stats"]["decode"][k] for k in (
            "decode_chunk", "host_syncs", "tokens", "tokens_per_sync",
            "chunk_s_total", "chunk_sizes")})}
        for edge, snap in run["counters"].items()}
    for spec in _specs():
        assert phase_ratio.read({"counters": old}, spec["args"]) is None


def test_a_misspelt_path_raises_on_a_program_that_has_the_counters(run):
    with pytest.raises(KeyError):
        phase_ratio.read(run, {"num": "decode.phase_s.no_such_phase",
                               "den": "decode.loop_s"})


def test_rehearsal_prints_the_seven(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k not in ("RAY_TPU_ARENA", "RAY_TPU_ADDRESS", "PYTHONPATH")}
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--rehearse", "--workload", CELL, "--seconds", "3"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    traced = lines[1]
    for spec in _specs():
        assert traced["rehearsal." + spec["name"]] > 0
    old = [k for k in traced if k.startswith(("rehearsal.engine.",
                                              "rehearsal.kernel."))]
    assert len(old) >= 3      # the metrics that were there are there still
