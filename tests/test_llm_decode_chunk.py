"""Fused multi-token decode parity (the r6 tentpole): a lax.scan chunk of
N on-device steps must be BIT-IDENTICAL to N per-step ticks — tokens,
logprobs, stream-queue contents — for both the row KVCache and the
PagedKVCache, including EOS hit mid-chunk, max_tokens hit mid-chunk, and
a slot finishing while its batch neighbors continue. The chunk fn splits
the PRNG key once per step exactly like the host loop did, so parity is
structural, not approximate.

Servers are memoized per (chunk, paged) and reused across tests: greedy
decode never consumes the sample key, so outputs are state-independent,
and reuse keeps the jit-variant compile bill paid once (tier-1 runs
against a wall clock). Only the SAMPLED parity test builds fresh servers
— it is exactly the test where key state matters.
"""

import asyncio

import numpy as np
import pytest

PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [11, 12, 13, 14]]

_SERVERS = {}
_BASE = {}


def _server(chunk, paged=False, fresh=False):
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    cfg = dict(preset="tiny", max_batch_slots=4, max_seq_len=128,
               decode_chunk=chunk, seed=0)
    if paged:
        cfg.update(paged=True, page_size=16)
    if fresh:
        return LLMServer(LLMConfig(**cfg))
    key = (chunk, paged)
    if key not in _SERVERS:
        _SERVERS[key] = LLMServer(LLMConfig(**cfg))
    return _SERVERS[key]


def _gen(srv, prompts, **kw):
    """Concurrent generates (admission order == list order)."""
    async def go():
        return await asyncio.gather(*[srv.generate(list(p), **kw)
                                      for p in prompts])
    return asyncio.run(go())


def _base(paged):
    """Per-step (chunk=1) greedy reference: tokens + logprobs."""
    if paged not in _BASE:
        _BASE[paged] = _gen(_server(1, paged), PROMPTS, max_tokens=12,
                            logprobs=True)
    return _BASE[paged]


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("chunk", [4, 8])
def test_greedy_parity(chunk, paged):
    got = _gen(_server(chunk, paged), PROMPTS, max_tokens=12,
               logprobs=True)
    for a, b in zip(_base(paged), got):
        assert a["tokens"] == b["tokens"]
        assert a["logprobs"] == b["logprobs"]  # bit-identical, not approx


def test_sampled_parity_dense():
    """Same seed → same key-split stream → identical SAMPLED tokens,
    regardless of how the steps are partitioned into chunks. Fresh servers:
    this is the one test where consumed key state would skew the compare."""
    kw = dict(max_tokens=10, temperature=1.3, top_p=0.9)
    base = _gen(_server(1, fresh=True), PROMPTS, **kw)
    got = _gen(_server(8, fresh=True), PROMPTS, **kw)
    for a, b in zip(base, got):
        assert a["tokens"] == b["tokens"]


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_eos_mid_chunk(paged):
    """Pick an EOS id the greedy stream emits at a non-chunk-boundary step;
    the chunked server must stop at exactly the same token."""
    ref = _base(paged)[0]["tokens"]
    eos = ref[5]  # inside the second chunk of 4, mid-chunk for 8 too
    stop = ref.index(eos)
    for chunk in (1, 4, 8):
        out = _gen(_server(chunk, paged), [PROMPTS[0]], max_tokens=12,
                   eos_id=eos, logprobs=True)[0]["tokens"]
        assert out == ref[:stop], (chunk, out)


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_mixed_budgets_slot_finishes_while_others_run(paged):
    """Slots with max_tokens 3/8/13 share the batch: the short one stops
    mid-chunk (termination masked in-scan) while its neighbors keep
    decoding to their own budgets."""
    budgets = [3, 8, 13]

    def run(chunk):
        srv = _server(chunk, paged)
        async def go():
            return await asyncio.gather(*[
                srv.generate(list(p), max_tokens=mt, logprobs=True)
                for p, mt in zip(PROMPTS, budgets)])
        return asyncio.run(go())

    base = run(1)
    for a, mt in zip(base, budgets):
        assert len(a["tokens"]) == mt
    for chunk in (4, 8):
        got = run(chunk)
        for a, b in zip(base, got):
            assert a["tokens"] == b["tokens"]
            assert a["logprobs"] == b["logprobs"]


# -- requests admitted while others decode (run-ahead engine) -----------------
# The engine dispatches chunk k+1 before it reads chunk k, and a prompt's first
# token joins its slot on the device: a late request must still get exactly its
# own solo greedy run, whatever ends it.
LATE_PROMPT = [21, 22, 23, 24, 25, 26]
LATE_CASES = {
    # name: (prompt, generate kwargs; eos_id "first" = the solo first token)
    "first_is_eos": (LATE_PROMPT, dict(max_tokens=9, eos_id="first")),
    "max_tokens_1": (LATE_PROMPT, dict(max_tokens=1)),
    "max_tokens_2": (LATE_PROMPT, dict(max_tokens=2)),
    "budget_mid_chunk": (LATE_PROMPT, dict(max_tokens=6)),
    # P + max_tokens == max_seq_len: the row is full at the last token
    "row_fills": (list(range(3, 3 + 117)), dict(max_tokens=11)),
}
_SOLO = {}


def _solo(paged, case):
    """The late request alone on the per-step (chunk=1) server."""
    if (paged, case) not in _SOLO:
        prompt, kw = LATE_CASES[case]
        kw = dict(kw, logprobs=True)
        if kw.get("eos_id") == "first":
            first = _gen(_server(1, paged), [prompt], max_tokens=1)[0]
            kw["eos_id"] = first["tokens"][0]
        _SOLO[(paged, case)] = (kw, _gen(_server(1, paged), [prompt], **kw)[0])
    return _SOLO[(paged, case)]


@pytest.mark.parametrize("case", sorted(LATE_CASES))
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("chunk", [4, 8])
def test_late_admission_parity(chunk, paged, case):
    """Two requests decode; a third is admitted mid-flight and ends by its
    first token being eos, max_tokens of 1 or 2, a budget that runs out
    inside a chunk, or a full row. Each of the three equals its solo run,
    tokens and logprobs, and every slot and page comes back."""
    srv = _server(chunk, paged)
    kw, want = _solo(paged, case)
    prompt = LATE_CASES[case][0]
    base = _base(paged)

    async def go():
        early = [asyncio.ensure_future(
            srv.generate(list(p), max_tokens=12, logprobs=True))
            for p in PROMPTS[:2]]
        # until both decode: their first chunks are in flight or read
        while (len(srv._active) < 2
               or any(len(s.generated) < 2 for s in srv._active.values())):
            await asyncio.sleep(0)
        late = await srv.generate(list(prompt), **kw)
        return late, await asyncio.gather(*early)

    late, early = asyncio.run(go())
    assert late["tokens"] == want["tokens"], case
    assert late["logprobs"] == want["logprobs"]
    if case == "first_is_eos":
        assert late["tokens"] == []
    else:
        assert len(late["tokens"]) == kw["max_tokens"]
    for a, b in zip(base, early):
        assert a["tokens"] == b["tokens"]
        assert a["logprobs"] == b["logprobs"]
    st = srv.stats()
    assert st["active"] == 0 and st["free_slots"] == 4
    assert not np.asarray(srv._slots.active).any()
    if paged:
        assert st["pages_in_use"] == st["prefix_cached_pages"]


def test_stream_queue_parity():
    """generate_stream consumers see the same tokens in the same order —
    the chunked loop flushes each slot's queue per chunk, in token order.
    (Queue flushing is host-side and cache-agnostic; dense covers it.)"""
    def run(chunk):
        srv = _server(chunk)
        async def drain(p):
            return [t async for t in srv.generate_stream(list(p),
                                                         max_tokens=9)]
        async def go():
            return await asyncio.gather(*[drain(p) for p in PROMPTS])
        return asyncio.run(go())

    base = run(1)
    assert all(len(s) == 9 for s in base)
    assert run(8) == base


def test_decode_stats_record_amortization():
    """stats()['decode'] proves the sync amortization: steady-state chunks
    of 8 push tokens_per_sync well above 1, and the adaptive loop used
    chunk 1 only while the prefill queue was non-empty."""
    d = _server(8).stats()["decode"]
    assert d["host_syncs"] < d["tokens"]
    assert d["tokens_per_sync"] > 1.0
    assert d["host_syncs_per_token"] <= 0.5
    assert 8 in d["chunk_sizes"]          # steady-state ran full chunks
    assert 1 in d["chunk_sizes"]          # prefill-overlap ticks stayed at 1
    assert d["chunk_ms_avg"] >= 0.0


def test_seq_capacity_terminates_in_scan():
    """Unit probe of the jitted chunk: a slot whose cache row has only 2
    positions of room must stop after 2 steps even though its token budget
    allows 8 — the max-seq-len rung of the in-scan termination mask."""
    import jax.numpy as jnp

    from ray_tpu.serve.llm import SlotState

    srv = _server(8)
    B = srv.config.max_batch_slots
    mask = np.zeros((B,), bool)
    mask[0] = True
    state = SlotState(
        last=jnp.full((B,), 3, jnp.int32), active=jnp.asarray(mask),
        budget=jnp.full((B,), 8, jnp.int32),   # budget: 8 tokens allowed
        room=jnp.asarray(np.where(mask, 2, 0).astype(np.int32)),  # room: 2
        eos=jnp.full((B,), -1, jnp.int32), temps=jnp.zeros((B,), jnp.float32),
        top_ps=jnp.ones((B,), jnp.float32), top_ks=jnp.zeros((B,), jnp.int32))
    cache, state, toks, n_valid, logps, key = srv._decode_chunk(
        srv.params, srv.cache, state, srv._sample_key, False, 8)
    # old cache was donated; the slot state the chunk hands on is the
    # engine's again: slot 0 ended in the chunk, with 6 of its budget left
    srv.cache, srv._sample_key, srv._slots = cache, key, state
    assert not np.asarray(state.active).any()
    assert int(state.budget[0]) == 6 and int(state.room[0]) == 0
    n_valid = np.asarray(n_valid)
    assert int(n_valid[0]) == 2
    assert all(int(n_valid[i]) == 0 for i in range(1, B))


def test_reconfigure_decode_chunk():
    """The serve user_config hook retunes the chunk length in place (the
    jit cache just gains a variant) — and parity still holds. Runs LAST in
    this file: it mutates the shared chunk-1 server's config."""
    srv = _server(1)
    srv.reconfigure({"decode_chunk": 8})
    assert srv.config.decode_chunk == 8
    got = _gen(srv, PROMPTS, max_tokens=12, logprobs=True)
    for a, b in zip(_base(False), got):
        assert a["tokens"] == b["tokens"]
    with pytest.raises(ValueError):
        srv.reconfigure({"decode_chunk": 0})
