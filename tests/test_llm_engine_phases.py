"""The serving engine accounts for its own tick loop (ISSUE 25): every stretch
of host time on the loop's thread belongs to one named phase, and each phase
goes to two sinks: a profiler annotation `engine.<key>` and exact counters
under `stats()["decode"]`.

One server per preset (`tiny`, `moe_tiny`) runs one workload with more
requests than slots and a page pool small enough to evict, demote and restore;
the tests read that run. A module's servers share one event loop: the engine's
`asyncio.Event`s bind to the loop they are first awaited on.
"""

import asyncio
import concurrent.futures
import glob
import os
import subprocess
import sys

import numpy as np
import pytest

from ray_tpu.util import tracing

# 6 distinct prompts of 4 to 5 pages of 8 tokens, then the first one again
# (its pages were evicted and demoted by then: a restore)
LENS = (28, 36, 25, 40, 33, 30)
MAX_TOKENS = 9
RUN_TIMEOUT_S = 120.0     # one workload takes a few seconds once compiled


class _Run:
    """A server after the workload, with what the test itself sampled at
    every decode sync (as the benchmark's deployment does, from outside)."""

    def __init__(self, preset, loop):
        from ray_tpu.serve.llm import LLMConfig, LLMServer
        self.loop = loop
        self.srv = LLMServer(LLMConfig(
            preset=preset, max_batch_slots=2, max_seq_len=64, paged=True,
            page_size=8, num_pages=15, prefill_chunk=16, decode_chunk=4,
            seed=0))
        self.sampled = []
        note_sync = self.srv._note_sync

        def sampling(tokens, dt_s, chunk=None):
            self.sampled.append(len(self.srv._active))
            return note_sync(tokens, dt_s, chunk)

        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, 250, n).tolist() for n in LENS]
        self.srv._note_sync = sampling
        try:
            self.outs = self.generate(prompts) + self.generate(prompts[:1])
        finally:
            self.srv._note_sync = note_sync
        self.prompts = prompts + prompts[:1]
        self.stats = self.srv.stats()

    def generate(self, prompts):
        async def go():
            return await asyncio.wait_for(asyncio.gather(*[
                self.srv.generate(p, max_tokens=MAX_TOKENS)
                for p in prompts]), RUN_TIMEOUT_S)
        return self.loop.run_until_complete(go())


@pytest.fixture(scope="module")
def loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


@pytest.fixture(scope="module", params=["tiny", "moe_tiny"])
def run(request, loop):
    r = _Run(request.param, loop)
    yield r
    r.srv.close()


def test_loop_phases_sum_to_loop_seconds(run):
    from ray_tpu.serve.llm import LOOP_PHASES
    d = run.stats["decode"]
    assert all(len(o["tokens"]) == MAX_TOKENS for o in run.outs)
    assert d["ticks"] > 0 and d["loop_s"] > 0
    top = sum(d["phase_s"][k] for k in LOOP_PHASES)
    assert abs(top - d["loop_s"]) <= 0.02 * d["loop_s"], (top, d["loop_s"])
    # one `yield` an iteration, and children never outlast their parents
    assert d["phase_n"]["yield"] == d["ticks"]
    assert d["phase_s"]["demote_stash"] <= d["phase_s"]["demote"]
    assert d["phase_s"]["demote"] <= d["phase_s"]["evict"]
    assert d["phase_s"]["evict"] <= d["phase_s"]["admit_allocate"]


def test_every_phase_that_must_have_run_ran(run):
    from ray_tpu.serve.llm import LOOP_PHASES, NESTED_PHASES
    d = run.stats["decode"]
    assert set(d["phase_s"]) == set(d["phase_n"]) == set(
        LOOP_PHASES + NESTED_PHASES)
    assert len(LOOP_PHASES + NESTED_PHASES) == 12
    for key in LOOP_PHASES + NESTED_PHASES:
        assert d["phase_n"][key] > 0 and d["phase_s"][key] > 0, key
    assert d["phase_n"]["decode_sync"] == d["host_syncs"]
    assert d["phase_n"]["decode_dispatch"] == d["host_syncs"]
    assert d["phase_n"]["prefill_dispatch"] == d["prefill_chunks"]
    assert d["phase_n"]["prefill_first_token"] == len(run.prompts)
    assert d["phase_n"]["admit_allocate"] == d["admitted"] == len(run.prompts)
    # an entry is an eviction pass that had pages to demote, not a page
    assert 0 < d["phase_n"]["demote"] == d["demote_passes"]
    assert d["phase_n"]["demote_stash"] == d["demote_passes"]
    assert d["demote_passes"] <= d["demoted_pages"]
    assert d["demote_passes"] <= d["phase_n"]["evict"]
    assert d["evicted_pages"] >= d["demoted_pages"]
    assert d["restored_pages"] > 0 and d["demote_failed"] == 0
    assert d["demote_last_error"] is None
    assert d["demote_bytes"] > 0 and d["stash_spilled_pages"] == 0
    assert d["decode_steps"] == sum(n * c for n, c in d["chunk_sizes"].items())
    # requests 3.. waited for one of the 2 slots
    assert d["slot_wait_s"] >= d["slot_wait_max_s"] > 0


def test_counters_are_all_there_at_zero_from_construction():
    """A reader takes window deltas and indexes both snapshots."""
    from ray_tpu.serve.llm import (LOOP_PHASES, NESTED_PHASES, LLMConfig,
                                   LLMServer)
    d = LLMServer(LLMConfig(preset="tiny", max_batch_slots=2, max_seq_len=32,
                            paged=False)).stats()["decode"]
    for key in ("loop_s", "ticks", "decode_steps", "active_slot_syncs",
                "prefill_chunks", "prefill_tokens", "prefill_padded_tokens",
                "continuation_chunks", "continuation_reach_keys",
                "continuation_query_keys",
                "admitted", "slot_wait_s", "slot_wait_max_s", "evicted_pages",
                "demoted_pages", "restored_pages", "demote_failed",
                "demote_bytes", "stash_spilled_pages", "host_syncs", "tokens",
                "demote_passes", "demote_wait_s", "demote_inflight_max_bytes",
                "restored_in_flight", "stash_worker_s", "run_ahead_chunks",
                "joined_on_device", "read_wait_s"):
        assert d[key] == 0, key
    assert d["demote_last_error"] is None
    assert d["phase_s"] == dict.fromkeys(LOOP_PHASES + NESTED_PHASES, 0.0)
    assert d["phase_n"] == dict.fromkeys(LOOP_PHASES + NESTED_PHASES, 0)


def test_occupancy_counter_equals_sampled_mean(run):
    d = run.stats["decode"]
    assert d["host_syncs"] == len(run.sampled)
    assert d["active_slot_syncs"] / d["host_syncs"] == pytest.approx(
        np.mean(run.sampled))


def test_prefill_tokens_are_the_uncached_prompt_tokens(run):
    d = run.stats["decode"]
    assert run.stats["prefix_hit_tokens"] > 0          # the repeated prompt
    assert d["prefill_tokens"] == (sum(map(len, run.prompts))
                                   - run.stats["prefix_hit_tokens"])
    assert d["prefill_padded_tokens"] >= d["prefill_tokens"]
    assert d["prefill_chunks"] >= len(run.prompts)


def test_decode_stats_hold_no_dotted_key(run):
    """`perfbench/readers/*` split their counter paths on `.`."""
    def keys(d):
        for k, v in d.items():
            yield str(k)
            if isinstance(v, dict):
                yield from keys(v)

    assert not [k for k in keys(run.stats["decode"]) if "." in k]


def test_failing_demote_cb_is_counted_and_serving_goes_on(run):
    srv = run.srv
    before = srv.stats()["decode"]

    def broken(pid, node):
        raise OSError("no space left on /dev/shm")

    good, srv.page_mgr.demote_cb = srv.page_mgr.demote_cb, broken
    try:
        rng = np.random.default_rng(11)
        outs = run.generate(
            [rng.integers(1, 250, 35).tolist() for _ in range(4)])
    finally:
        srv.page_mgr.demote_cb = good
    after = srv.stats()["decode"]
    assert all(len(o["tokens"]) == MAX_TOKENS for o in outs)
    failed = after["demote_failed"] - before["demote_failed"]
    assert failed > 0
    assert failed == ((after["evicted_pages"] - before["evicted_pages"])
                      - (after["demoted_pages"] - before["demoted_pages"]))
    assert after["demote_last_error"].startswith("OSError")
    assert "no space left on /dev/shm" in after["demote_last_error"]


def test_importing_tracing_leaves_jax_out():
    """The driver, the controller and the node agent import util.tracing and
    must never import jax: on libtpu that takes the chip from the workers."""
    code = ("import sys; import ray_tpu.util.tracing as t; "
            "tot = t.PhaseTotals('engine', ['a']); "
            "exec('with t.phase(tot, \"a\"): pass'); "
            "assert tot.counts['a'] == 1; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    assert subprocess.run([sys.executable, "-c", code], timeout=120,
                          env=dict(os.environ)).returncode == 0


def test_phase_accumulates_nests_and_survives_an_exception():
    tot = tracing.PhaseTotals("engine", ("outer", "inner"))
    assert tot.seconds == {"outer": 0.0, "inner": 0.0}
    assert tot.counts == {"outer": 0, "inner": 0}
    assert tot.names["inner"] == "engine.inner"
    with pytest.raises(ValueError):
        with tracing.phase(tot, "outer"):
            with tracing.phase(tot, "inner"):
                pass
            with tracing.phase(tot, "inner"):
                raise ValueError("x")
    assert tot.counts == {"outer": 1, "inner": 2}
    assert tot.seconds["outer"] >= tot.seconds["inner"] > 0
    before = len(tracing.events())
    with tracing.phase(tot, "outer"):
        pass
    assert len(tracing.events()) == before        # nothing in the ring


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return names


def test_profiler_host_plane_holds_engine_phases(run, tmp_path):
    """Under a profiler session the phases are events on the host plane of
    the trace, on the profiler's own clock (options as the benchmark's
    `Tracer.start` sets them)."""
    import jax
    rng = np.random.default_rng(13)
    prompts = [rng.integers(1, 250, 35).tolist() for _ in range(4)]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        run.generate(prompts)
        # the stash's thread ends its last `stash.put` inside the session
        concurrent.futures.wait([h[0] for h in run.srv._tier._handoffs], 60.0)
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(str(tmp_path))
    assert "stash.put" in names       # on the stash's own thread
    for key in ("decode_sync", "demote", "demote_stash", "evict",
                "admit_allocate", "yield", "decode_dispatch",
                "prefill_dispatch", "prefill_first_token"):
        assert "engine." + key in names, (key, sorted(
            n for n in names if n.startswith("engine.")))
