"""KV demotion off the engine loop (ISSUE 26): an eviction pass dispatches ONE
kind of gather program for its pages and hands the result to the stash's own
thread; the loop waits for neither the transfer nor the stash. What must hold:
a restored page is byte for byte the demoted page wherever it is met (still in
flight, in shared memory, on disk), although the admitting request's prefill
wrote its pool slot right after the eviction; an exception on the stash's
thread is counted and serving goes on; nothing compiles after construction.

A module's servers share one event loop: the engine's `asyncio.Event`s bind to
the loop they are first awaited on.
"""

import asyncio
import concurrent.futures
import functools
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from ray_tpu._private import object_store
from ray_tpu.serve import kv_transfer
from ray_tpu.serve.kv_transfer import DemotionTier, KVPageStash
from ray_tpu.serve.llm import LLMConfig, LLMServer

PRESETS = ("tiny", "moe_tiny")
MAX_TOKENS = 9
WAIT_S = 120.0
# 5 distinct prompts that take 5 or 6 pages of 8 tokens (prompt and answer),
# 3 of them full prompt pages that stay cached: with 14 usable pages the fifth
# admission evicts every page of the first prompt, and its prefill writes them
LENS = (28, 25, 26, 27, 33)


class _Engine:
    def __init__(self, preset, loop, num_pages=15):
        self.loop = loop
        self.srv = LLMServer(LLMConfig(
            preset=preset, max_batch_slots=2, max_seq_len=64, paged=True,
            page_size=8, num_pages=num_pages, prefill_chunk=16,
            decode_chunk=4, seed=0))
        rng = np.random.default_rng(3)
        self.prompts = [rng.integers(1, 250, n).tolist() for n in LENS]

    def generate(self, prompt):
        return self.loop.run_until_complete(asyncio.wait_for(
            self.srv.generate(prompt, max_tokens=MAX_TOKENS), WAIT_S))

    def cached_pages(self, prompt):
        """[(node, page id, k bytes, v bytes)] of the prompt's cached pages."""
        out = []
        for node in self.srv.page_mgr._walk(prompt):
            assert node.page is not None
            out.append((node, node.page) + self.page_bytes(node.page))
        return out

    def page_bytes(self, pid):
        c = self.srv.cache
        return (np.asarray(c.k_pages[:, :, pid]).tobytes(),
                np.asarray(c.v_pages[:, :, pid]).tobytes())

    def page_nbytes(self):
        k = self.srv.cache.k_pages
        return 2 * k.dtype.itemsize * int(np.prod(k.shape[:2] + k.shape[3:]))

    def settle(self):
        """Wait for the stash's thread to finish what it was handed, then
        let the engine reap it; returns the engine's counters."""
        concurrent.futures.wait([h[0] for h in self.srv._tier._handoffs], WAIT_S)
        return self.srv.stats()["decode"]

    def close(self):
        self.srv.close()


@pytest.fixture(scope="module")
def loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


@pytest.fixture(params=PRESETS)
def engine(request, loop, monkeypatch):
    monkeypatch.delenv("RAY_TPU_ARENA", raising=False)
    e = _Engine(request.param, loop)
    yield e
    e.close()


class _Gate:
    """Holds the stash's thread until opened, so that what the loop hands
    over stays in flight."""

    def __init__(self, stash):
        self._open = threading.Event()
        self._held = stash._worker.submit(self._open.wait, WAIT_S)

    def open(self):
        self._open.set()
        assert self._held.result(WAIT_S)


@pytest.mark.parametrize("tier", ["shm", "in_flight", "disk"])
def test_restored_page_is_the_demoted_page(engine, tier):
    srv, stash = engine.srv, engine.srv._kv_stash
    first, *others = engine.prompts
    out = engine.generate(first)
    for p in others[:-1]:
        engine.generate(p)
    before = engine.cached_pages(first)
    assert len(before) == 3 and srv.page_mgr.evicted_pages == 0
    if tier == "disk":
        stash.budget = engine.page_nbytes()    # all but the newest spill
    gate = _Gate(stash) if tier == "in_flight" else None
    engine.generate(others[-1])     # evicts `first`; its prefill reuses them
    handles = [node.handle for node, _, _, _ in before]
    assert all(node.page is None for node, _, _, _ in before)
    assert all(h is not None for h in handles)
    # the pool slots hold the admitting request's KV by now
    assert all(engine.page_bytes(pid) != (k, v) for _, pid, k, v in before)
    if gate is None:
        d = engine.settle()
        assert d["demote_failed"] == 0 and not srv._tier._staged
        assert [h["oid"] in stash._disk for h in handles] == (
            [tier == "disk"] * 3)
        assert d["stash_spilled_pages"] == (
            d["demoted_pages"] - 1 if tier == "disk" else 0)
    else:
        assert all(h["oid"] in srv._tier._staged for h in handles)

    again = engine.generate(first)             # restores the three pages
    if gate is not None:
        gate.open()
    d = engine.settle()
    assert d["restored_pages"] == 3 and d["demote_failed"] == 0
    assert d["restored_in_flight"] == (3 if tier == "in_flight" else 0)
    assert again["tokens"] == out["tokens"]
    for node, _, k, v in before:
        assert node.page is not None
        assert engine.page_bytes(node.page) == (k, v)
    # and once it is there, what the stash holds is those bytes too
    for handle, (_, _, k, v) in zip(handles, before):
        got_k, got_v = stash.get(handle)
        assert (got_k.tobytes(), got_v.tobytes()) == (k, v)
    assert d["demote_passes"] > 0 and d["stash_worker_s"] > 0
    assert d["demote_wait_s"] == 0
    assert 0 < d["demote_inflight_max_bytes"] <= kv_transfer.STAGED_CAP_BYTES


def test_exception_on_the_stash_thread_is_counted_and_serving_goes_on(engine):
    srv, stash = engine.srv, engine.srv._kv_stash
    first, *others = engine.prompts
    out = engine.generate(first)
    calls, seal = [], stash._seal

    def every_other_one_fails(handle, k_page, v_page):
        calls.append(handle["oid"])
        if len(calls) % 2:
            raise OSError("no space left on /dev/shm")
        return seal(handle, k_page, v_page)

    stash._seal = every_other_one_fails
    try:
        outs = [engine.generate(p) for p in others + others[:1]]
        d = engine.settle()
    finally:
        stash._seal = seal
    assert all(len(o["tokens"]) == MAX_TOKENS for o in outs)
    assert d["demote_failed"] == (len(calls) + 1) // 2 > 0
    assert d["demoted_pages"] == len(calls) // 2 > 0
    assert d["demote_failed"] == d["evicted_pages"] - d["demoted_pages"]
    assert d["demote_last_error"].startswith("OSError")
    assert "no space left on /dev/shm" in d["demote_last_error"]
    # a page that never reached the stash is not offered for a restore: the
    # first prompt lost its first page, so it prefills from the start
    hit = srv.page_mgr.prefix_hit_tokens
    again = engine.generate(first)
    assert srv.page_mgr.prefix_hit_tokens == hit
    assert again["tokens"] == out["tokens"]


def test_loop_waits_for_the_oldest_hand_off_over_the_cap(engine, monkeypatch):
    """Back-pressure: with the stash's thread slower than the evictions and
    room for four staged pages, the loop waits for the oldest hand-off and
    counts the wait."""
    srv, stash = engine.srv, engine.srv._kv_stash
    seal = stash._seal

    def slow(handle, k_page, v_page):
        time.sleep(0.02)
        return seal(handle, k_page, v_page)

    stash._seal = slow
    page_bytes = engine.page_nbytes()
    monkeypatch.setattr(srv._tier, "staged_cap_bytes", 4 * page_bytes)
    try:
        for p in engine.prompts + engine.prompts[:2]:
            engine.generate(p)
        d = engine.settle()
    finally:
        stash._seal = seal
    assert d["demote_failed"] == 0 and d["demoted_pages"] > 8
    assert d["demote_wait_s"] > 0
    # the cap bounds what was staged before a hand-off, so the most ever
    # staged is under the cap plus the group that was let in
    assert d["demote_inflight_max_bytes"] <= (
        4 + kv_transfer.DEMOTE_GROUP) * page_bytes


@pytest.fixture(scope="module")
def compile_events():
    """jax's own monitoring events, as the benchmark's CompileMeter reads
    them: one `backend_compile_duration` a program compiled or fetched."""
    import jax
    seen = []

    def on_duration(event, duration, **_):
        if event.endswith("/backend_compile_duration"):
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return seen


class _Bare:
    """A `DemotionTier` over a `PagedKVCache` filled with noise: no engine,
    no model, no event loop. `failed` collects what the tier reports back
    where an engine's page manager would stand."""

    def __init__(self, layout, seed, num_pages=24, staged_cap_bytes=None):
        import jax.numpy as jnp

        from ray_tpu.ops.paged_attention import PagedKVCache
        from ray_tpu.serve.llm import NESTED_PHASES
        from ray_tpu.util.tracing import PhaseTotals
        self.rng = np.random.default_rng(seed)
        self.cache = PagedKVCache.init(
            2, 2, 16, num_pages, 8, 2, 8, dtype=jnp.float32,
            index_dim=8 if layout == "indexed" else 0)
        self.failed = []
        self.phases = PhaseTotals("engine", NESTED_PHASES)
        self.tier = DemotionTier(
            lambda: self.cache, self.phases,
            lambda node, handle, error: self.failed.append((node, error)),
            staged_cap_bytes)
        self.cache = self.tier.warm(self.cache)
        self.fill()

    def fill(self):
        import jax.numpy as jnp
        self.cache = self.cache.with_pools(tuple(
            jnp.asarray(self.rng.normal(size=p.shape).astype(np.float32))
            for p in self.cache.pools()))

    def page_bytes(self, pid):
        """One page's bytes in every pool (two dense, three indexed)."""
        import jax.numpy as jnp
        return [np.asarray(jnp.take(p, pid, axis=self.cache.page_axis)
                           ).tobytes() for p in self.cache.pools()]

    def settle(self):
        concurrent.futures.wait([h[0] for h in self.tier._handoffs], WAIT_S)
        self.tier.reap()
        return self.tier.counters


@pytest.mark.parametrize("n_pages", [1, 7, 20])
@pytest.mark.parametrize("layout", ["dense", "indexed"])
def test_pass_of_any_size_compiles_nothing_and_is_exact(
        layout, n_pages, compile_events, monkeypatch):
    monkeypatch.delenv("RAY_TPU_ARENA", raising=False)
    b = _Bare(layout, seed=n_pages)
    tier, stash = b.tier, b.tier.stash
    try:
        pids = b.rng.permutation(np.arange(1, 24))[:n_pages].tolist()
        want = [b.page_bytes(pid) for pid in pids]
        assert len(want[0]) == (3 if layout == "indexed" else 2)
        before = len(compile_events)
        handles = [tier.demote_page(pid, object()) for pid in pids]
        tier.demote_pass()
        assert len(tier._handoffs) == -(-n_pages // kv_transfer.DEMOTE_GROUP)
        d = b.settle()
        assert len(compile_events) == before
        assert d["demote_passes"] == b.phases.counts["demote"] == 1
        assert not b.failed
        assert d["demote_bytes"] == sum(h["nbytes"] for h in handles)
        for handle, page in zip(handles, want):
            got = stash.get(handle)
            assert got[0].dtype == b.cache.k_pages.dtype
            assert [list(g.shape) for g in got] == [
                blk["shape"] for blk in handle["blocks"]]
            assert [g.tobytes() for g in got] == page
        # and back in, to other pages, by the one restore program
        b.fill()
        before = len(compile_events)
        for handle, pid in zip(handles, reversed(pids)):
            assert tier.restore_page(handle, pid)
        b.cache = tier.flush_restores(b.cache)
        assert [b.page_bytes(pid) for pid in reversed(pids)] == want
        assert len(compile_events) == before
        assert d["restored_in_flight"] == 0 and not tier._pending_restores
    finally:
        tier.close()


def test_tier_alone_waits_for_the_oldest_hand_off_over_the_cap(monkeypatch):
    """The loop-side wait, against a tier and nothing else: room for two
    staged pages, a stash thread slower than the hand-offs, three groups."""
    monkeypatch.delenv("RAY_TPU_ARENA", raising=False)
    b = _Bare("dense", seed=5)
    tier, stash, seal = b.tier, b.tier.stash, b.tier.stash._seal
    page_bytes = tier.stash.new_handle(tier._layout)["nbytes"]
    tier.staged_cap_bytes = 2 * page_bytes

    def slow(handle, *blocks):
        time.sleep(0.02)
        return seal(handle, *blocks)

    stash._seal = slow
    try:
        pids = list(range(1, 21))
        want = [b.page_bytes(pid) for pid in pids]
        handles = [tier.demote_page(pid, object()) for pid in pids]
        tier.demote_pass()
        # the first group went in over the cap (nothing to wait for); each
        # later one waited until the one before it was sealed
        assert len(tier._handoffs) == 1
        d = b.settle()
        assert d["demote_wait_s"] > 0 and not b.failed
        assert d["demote_inflight_max_bytes"] == (
            kv_transfer.DEMOTE_GROUP * page_bytes)
        assert tier.staged_bytes == 0 and not tier._staged
        assert [[g.tobytes() for g in stash.get(h)] for h in handles] == want
    finally:
        stash._seal = seal
        tier.close()


def test_tier_reports_what_the_stash_refused_and_close_leaves_nothing(
        monkeypatch):
    """An exception on the stash's thread reaches `failed` with the node;
    `close()` waits for what is in flight, reports it, and leaves no
    segment. Twice is fine."""
    monkeypatch.delenv("RAY_TPU_ARENA", raising=False)
    b = _Bare("indexed", seed=9)
    tier, stash, seal = b.tier, b.tier.stash, b.tier.stash._seal

    def third_fails(handle, *blocks):
        if handle is handles[2]:
            raise OSError("no space left on /dev/shm")
        return seal(handle, *blocks)

    stash._seal = third_fails
    gate = _Gate(stash)
    nodes = [object() for _ in range(5)]
    handles = [tier.demote_page(pid, node)
               for pid, node in zip(range(1, 6), nodes)]
    tier.demote_pass()
    assert tier.staged_bytes == sum(h["nbytes"] for h in handles)
    threading.Timer(0.2, gate.open).start()
    tier.close()                     # waits for the hand-off, then reaps it
    assert [(node, type(e)) for node, e in b.failed] == [(nodes[2], OSError)]
    assert tier.staged_bytes == 0 and not tier._staged
    assert stash.tier_stats() == {"shm_objects": 0, "shm_bytes": 0,
                                  "disk_objects": 0, "disk_bytes": 0}
    for handle in handles:
        name = object_store.seg_name(handle["oid"])
        assert not os.path.exists(os.path.join("/dev/shm", name))
    tier.close()


@pytest.mark.parametrize("preset", PRESETS)
def test_new_counters_are_there_at_zero_from_construction(
        preset, loop, monkeypatch):
    monkeypatch.delenv("RAY_TPU_ARENA", raising=False)
    e = _Engine(preset, loop)
    try:
        d = e.srv.stats()["decode"]
        for key in ("demote_passes", "demote_wait_s", "restored_in_flight",
                    "demote_inflight_max_bytes", "stash_worker_s",
                    "demote_bytes", "demoted_pages", "demote_failed"):
            assert d[key] == 0, key
        assert e.srv._kv_stash.phases.names == {"put": "stash.put"}
        for p in e.prompts:
            e.generate(p)
        after = e.settle()
        for key in ("demote_passes", "demote_inflight_max_bytes",
                    "stash_worker_s", "demote_bytes", "demoted_pages"):
            assert after[key] > 0, key
    finally:
        e.close()


def test_close_with_puts_queued_leaves_no_segment_and_no_spill_file(
        monkeypatch):
    monkeypatch.delenv("RAY_TPU_ARENA", raising=False)
    shape = (2, 3, 8, 8)
    one_page = 2 * int(np.prod(shape)) * 4
    stash = KVPageStash(budget_bytes=2 * one_page)   # the rest goes to disk
    gate = _Gate(stash)
    rng = np.random.default_rng(0)
    handles, puts = [], []
    for _ in range(3):
        k = rng.normal(size=(4,) + shape).astype(np.float32)
        layout = [{"shape": list(shape), "dtype": "float32"}] * 2
        group = [stash.new_handle(layout) for _ in range(3)]
        puts.append(stash.put(group, k, k + 1))      # row 3 is padding
        handles += group
    assert not any(p.done() for p in puts)
    threading.Timer(0.2, gate.open).start()
    stash.close()                                    # runs the queue first
    assert [p.result(0) for p in puts] == [[None] * 3] * 3
    assert stash.spilled_pages == 7
    assert stash.tier_stats() == {"shm_objects": 0, "shm_bytes": 0,
                                  "disk_objects": 0, "disk_bytes": 0}
    for handle in handles:
        name = object_store.seg_name(handle["oid"])
        assert not os.path.exists(os.path.join("/dev/shm", name))
        assert not os.path.exists(os.path.join(
            object_store._spill_dir(), name))
    stash.close()                                    # closing twice is fine
    with pytest.raises(RuntimeError):
        stash.put([], k, k)


# stats() of a paged prefix-caching server at the parent commit (4508f22),
# group by group: the tier's counters keep their names and their place
_PHASE_KEYS = {"admit_allocate", "decode_build", "decode_dispatch",
               "decode_emit", "decode_sync", "demote", "demote_stash",
               "evict", "prefill_dispatch", "prefill_first_token", "restore",
               "yield"}
_STALL_KEYS = {"decode_sync", "prefill_first_token"}
PARENT_STATS_KEYS = {
    "": {"active", "decode", "free_slots", "pages_free", "pages_in_use",
         "prefix_cached_pages", "prefix_hit_rate", "prefix_hit_tokens",
         "prefix_query_tokens", "radix", "requests", "slo", "stalls"},
    "decode": {
        "active_slot_syncs", "admitted", "chunk_ms_avg", "chunk_s_total",
        "chunk_sizes", "continuation_chunks", "continuation_query_keys",
        "continuation_reach_keys", "decode_chunk", "decode_steps",
        "demote_bytes", "demote_failed", "demote_inflight_max_bytes",
        "demote_last_error", "demote_passes", "demote_wait_s",
        "demoted_pages", "evicted_pages", "host_syncs",
        "host_syncs_per_token", "joined_on_device", "loop_s", "phase_n",
        "phase_s", "prefill_chunks", "prefill_padded_tokens",
        "prefill_tokens", "read_wait_s", "restored_in_flight",
        "restored_pages", "rotary_split_projections", "run_ahead_chunks",
        "slot_wait_max_s", "slot_wait_s", "stall_max_s", "stall_n",
        "stall_s", "stash_spilled_pages", "stash_worker_s", "ticks",
        "tokens", "tokens_per_sync"},
    "decode.phase_n": _PHASE_KEYS, "decode.phase_s": _PHASE_KEYS,
    "decode.stall_n": _STALL_KEYS, "decode.stall_s": _STALL_KEYS,
    "radix": {"demoted_nodes", "demoted_pages", "evicted_pages",
              "prefix_nodes", "resident_pages", "restored_pages", "stash"},
    "radix.stash": {"disk_bytes", "disk_objects", "shm_bytes",
                    "shm_objects"},
    "slo": {"batch_occupancy", "kv_page_util", "radix", "spill_restore_ms",
            "tpot_ms", "ttft_s"},
    "slo.radix": {"prefix_evicted_pages", "prefix_hit_tokens",
                  "prefix_nodes"},
}


def _stats_keys(stats):
    at = lambda path: functools.reduce(  # noqa: E731
        lambda d, k: d[k], filter(None, path.split(".")), stats)
    return {path: set(at(path)) for path in PARENT_STATS_KEYS}


def test_stats_keys_and_span_names_are_the_parents(loop, monkeypatch):
    monkeypatch.delenv("RAY_TPU_ARENA", raising=False)
    e = _Engine("tiny", loop)
    try:
        assert _stats_keys(e.srv.stats()) == PARENT_STATS_KEYS
        for p in e.prompts + e.prompts[:1]:       # evicts, demotes, restores
            e.generate(p)
        e.settle()
        assert _stats_keys(e.srv.stats()) == PARENT_STATS_KEYS
        # the tier's phases are the engine's own: the same spans in a trace
        assert e.srv._tier._phases is e.srv._phases
        assert {k: e.srv._phases.names[k]
                for k in ("demote", "demote_stash", "restore")} == {
            "demote": "engine.demote", "demote_stash": "engine.demote_stash",
            "restore": "engine.restore"}
        n = e.srv.stats()["decode"]["phase_n"]
        assert n["demote"] > 0 and n["demote_stash"] > 0 and n["restore"] > 0
    finally:
        e.close()


def test_server_close_gives_back_the_stash_and_stats_still_answers(
        loop, monkeypatch):
    monkeypatch.delenv("RAY_TPU_ARENA", raising=False)
    e = _Engine("tiny", loop)
    srv, stash = e.srv, e.srv._kv_stash
    assert stash is srv._tier.stash
    for p in e.prompts:
        e.generate(p)
    before = e.settle()
    names = [object_store.seg_name(oid) for oid in stash._shm]
    assert names and all(
        os.path.exists(os.path.join("/dev/shm", n)) for n in names)
    srv.close()
    assert not any(os.path.exists(os.path.join("/dev/shm", n)) for n in names)
    with pytest.raises(RuntimeError):
        stash.put([], np.zeros(1))
    srv.close()                                  # closing twice is fine
    # a closed server still answers stats(): the counters as they stood,
    # the stash empty
    after = srv.stats()
    assert _stats_keys(after) == PARENT_STATS_KEYS
    assert after["decode"]["demote_bytes"] == before["demote_bytes"] > 0
    assert after["radix"]["stash"] == {"shm_objects": 0, "shm_bytes": 0,
                                       "disk_objects": 0, "disk_bytes": 0}
    # an engine with no tier has nothing to give back, and counts zeros
    dense = LLMServer(LLMConfig(preset="tiny", max_batch_slots=1,
                                max_seq_len=32))
    assert dense._tier is None and dense._kv_stash is None
    dense.close()
    assert all(dense.stats()["decode"][k] == 0
               for k in kv_transfer.TIER_COUNTERS)


def test_the_tier_module_imports_without_jax():
    """`kv_transfer.py` (and `llm.py`, which imports it) stay importable in
    a process that must not touch the chip: the tier takes jax in `warm`."""
    code = ("import sys, ray_tpu.serve.kv_transfer, ray_tpu.serve.llm; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr
