"""The serving engine runs one program ahead of what it reads (ISSUE 29): the
state a decode chunk starts from is carried on the device, so chunk k+1 is
dispatched before chunk k is read; a prompt's first token joins its slot on
the device and reaches its stream before the chunk dispatched after it is
read; a slot that ends in chunk k is released once, a chunk later; a failure
with a chunk in flight fails every waiter; a speculating engine still reads
what it dispatched at once.

Tiny preset on the CPU: what is asserted is the ORDER of the engine's calls
and its counters, never a time.
"""

import asyncio

import numpy as np
import pytest

RUN_TIMEOUT_S = 120.0


def _server(**kw):
    from ray_tpu.serve.llm import LLMConfig, LLMServer
    cfg = dict(preset="tiny", max_batch_slots=4, max_seq_len=128,
               paged=True, page_size=16, prefill_chunk=32, decode_chunk=4,
               seed=0)
    cfg.update(kw)
    return LLMServer(LLMConfig(**cfg))


def _prompts(n, seed=5, lo=5, hi=40):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 250, int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


N_BUSY = 12


def _budget(k):
    """Unequal answers, so that slots end (and prompts join) one at a time
    and the engine always has a chunk to run ahead of."""
    return 7 + 6 * (k % 4) + k


class _Log:
    """The order of the engine's dispatches, reads, syncs and releases, from
    outside it (as the benchmark's deployment wraps `_note_sync`)."""

    def __init__(self, srv):
        self.srv, self.events = srv, []
        self.not_streamed, self.first_emitted = [], set()
        dispatch, read, emit = (srv._decode_chunk, srv._read_chunk,
                                srv._emit_one)
        note_sync, release = srv._note_sync, srv._release_slot
        spec = srv._spec

        def dispatching(params, cache, state, key, want_logp, n):
            self.events.append(("dispatch", n))
            return dispatch(params, cache, state, key, want_logp, n)

        def speculating(*a):
            self.events.append(("dispatch", None))
            return spec(*a)

        def reading():
            chunk = srv._inflight[0]
            # every slot this chunk decodes has had its first token put on
            # its stream before the host blocks on the chunk
            self.not_streamed += [
                s.request_id for _, s in chunk.slots
                if s.request_id not in self.first_emitted]
            self.events.append(("read", chunk.n, chunk.seq))
            return read()

        def emitting(slot, tok, lp):
            first = not slot.generated
            done = emit(slot, tok, lp)
            if first:
                assert slot.stream_queue is None or slot.stream_queue.qsize()
                self.first_emitted.add(slot.request_id)
            return done

        def noting(tokens, dt_s, chunk=None):
            self.events.append(("sync", chunk, tokens))
            return note_sync(tokens, dt_s, chunk)

        def releasing(i):
            out = release(i)
            self.events.append(("release", i, np.asarray(
                srv.cache.block_tables[i]).tolist()
                if srv.page_mgr is not None else None))
            return out

        srv._decode_chunk, srv._read_chunk = dispatching, reading
        srv._note_sync, srv._release_slot = noting, releasing
        srv._emit_one = emitting
        if spec is not None:
            srv._spec = speculating

    def of(self, kind):
        return [e for e in self.events if e[0] == kind]


def _run(coro):
    return asyncio.run(asyncio.wait_for(coro, RUN_TIMEOUT_S))


@pytest.fixture(scope="module")
def busy():
    """More requests than slots, streamed and not, on a paged engine."""
    srv = _server()
    log = _Log(srv)
    prompts = _prompts(N_BUSY)

    async def stream(k, p):
        out = []
        async for tok in srv.generate_stream(p, max_tokens=_budget(k)):
            out.append(tok)
        return out

    async def go():
        jobs = [stream(k, p) if k % 2
                else srv.generate(p, max_tokens=_budget(k))
                for k, p in enumerate(prompts)]
        return await asyncio.gather(*jobs)

    outs = _run(go())
    return srv, log, prompts, outs


def test_chunk_k_plus_1_is_dispatched_before_chunk_k_is_read(busy):
    srv, log, _, _ = busy
    d = srv.stats()["decode"]
    order = [e for e in log.events if e[0] in ("dispatch", "read")]
    n_disp = 0
    ahead = 0
    for e in order:
        if e[0] == "dispatch":
            n_disp += 1
        else:
            # chunk `seq` is read with `n_disp` dispatched: one ahead when a
            # later chunk is already out
            assert e[2] <= n_disp
            ahead += e[2] < n_disp
    reads = log.of("read")
    assert [e[2] for e in reads] == list(range(1, len(reads) + 1))
    assert len(reads) == n_disp == d["host_syncs"]
    assert ahead / len(reads) > 0.9
    assert d["run_ahead_chunks"] / d["host_syncs"] > 0.9
    assert d["run_ahead_chunks"] == ahead
    assert d["phase_n"]["decode_dispatch"] == d["phase_n"]["decode_sync"]


def test_outputs_equal_each_requests_solo_run(busy):
    srv, _, prompts, outs = busy
    solo = _server()
    for k, (p, out) in enumerate(zip(prompts, outs)):
        want = _run(solo.generate(p, max_tokens=_budget(k)))["tokens"]
        got = out if k % 2 else out["tokens"]
        assert got == want, k


def test_first_token_is_streamed_before_the_next_chunk_is_read(busy):
    srv, log, prompts, _ = busy
    d = srv.stats()["decode"]
    assert log.not_streamed == []
    assert d["joined_on_device"] == len(prompts)
    assert d["phase_n"]["prefill_first_token"] == len(prompts)
    assert d["read_wait_s"] >= 0.0


def test_note_sync_once_a_chunk_with_its_length(busy):
    srv, log, _, _ = busy
    assert [e[1] for e in log.of("sync")] == [e[1] for e in log.of("read")]
    assert [e[1] for e in log.of("dispatch")] == [e[1] for e in log.of("read")]
    # a sync follows the read of its own chunk, before the next read
    kinds = [e[0] for e in log.events if e[0] in ("read", "sync")]
    assert kinds == ["read", "sync"] * (len(kinds) // 2)
    d = srv.stats()["decode"]
    assert d["decode_steps"] == sum(e[1] for e in log.of("sync"))
    assert d["tokens"] == sum(e[2] for e in log.of("sync"))


def test_finished_slot_is_released_once_with_its_table_row_zeroed(busy):
    srv, log, prompts, _ = busy
    releases = log.of("release")
    assert len(releases) == len(prompts)          # once a request
    for _, i, row in releases:
        assert not any(row), (i, row)             # zeroed behind chunk k+1
    assert sorted(srv._free) == [0, 1, 2, 3]
    assert not np.asarray(srv.cache.block_tables).any()
    assert not np.asarray(srv._slots.active).any()
    assert not srv._inflight and not srv._first_pending


def test_consumer_that_walks_away_leaves_the_device_state():
    """Only the host knows: the slot is taken out of the carried state, its
    pages come back, and its neighbour decodes on undisturbed."""
    srv = _server(prefix_cache=False)
    p0, p1 = _prompts(2, seed=9)
    want = _run(_server(prefix_cache=False).generate(p1, max_tokens=24))

    async def go():
        other = asyncio.ensure_future(srv.generate(p1, max_tokens=24))
        gen = srv.generate_stream(p0, max_tokens=60)
        got = []
        async for tok in gen:
            got.append(tok)
            if len(got) == 3:
                break
        await gen.aclose()
        return got, await other

    got, other = _run(go())
    assert len(got) == 3
    assert other["tokens"] == want["tokens"]
    st = srv.stats()
    assert st["active"] == 0 and st["free_slots"] == 4
    assert st["pages_in_use"] == 0
    assert not np.asarray(srv._slots.active).any()
    assert st["decode"]["tokens"] < 60          # it did not decode to the end


def test_chunk_that_raises_with_another_in_flight_fails_every_waiter():
    srv = _server()
    real = srv._decode_chunk
    calls = []

    def failing(*a):
        calls.append(len(srv._inflight))
        if len(calls) == 4:
            raise RuntimeError("device fell over")
        return real(*a)

    srv._decode_chunk = failing
    prompts = _prompts(6, seed=11)

    async def go():
        return await asyncio.gather(
            *[srv.generate(p, max_tokens=30) for p in prompts],
            return_exceptions=True)

    outs = _run(go())
    assert calls[3] == 1                        # one chunk was in flight
    # every request the engine held fails; those still waiting for a slot
    # are admitted to the freed slots and served by a new loop
    assert all(isinstance(o, RuntimeError) for o in outs[:4]), outs[:4]
    solo = _server()
    for p, out in zip(prompts[4:], outs[4:]):
        assert out["tokens"] == _run(solo.generate(p, max_tokens=30))["tokens"]
    assert not srv._active and not srv._prefill_q
    assert not srv._inflight and not srv._first_pending
    assert sorted(srv._free) == [0, 1, 2, 3]
    assert srv.stats()["pages_in_use"] == srv.stats()["prefix_cached_pages"]
    assert not np.asarray(srv._slots.active).any()


def test_speculating_engine_reads_before_it_dispatches():
    srv = _server(paged=False, speculate=4, decode_chunk=4)
    log = _Log(srv)
    prompts = [[3, 4, 5, 6] * 6, [9, 8, 7] * 5, _prompts(1)[0]]

    async def go():
        return await asyncio.gather(*[
            srv.generate(p, max_tokens=20) for p in prompts])

    outs = _run(go())
    plain = _server(paged=False)
    for p, out in zip(prompts, outs):
        assert out["tokens"] == _run(plain.generate(p, max_tokens=20))["tokens"]
    order = [e[0] for e in log.events if e[0] in ("dispatch", "read")]
    assert order == ["dispatch", "read"] * (len(order) // 2)
    d = srv.stats()["decode"]
    assert d["run_ahead_chunks"] == 0
    assert d["host_syncs"] == len(order) // 2
    assert srv.stats()["speculation"]["spec_ticks"] > 0
    # nothing joined ahead of the host: every first token was read at once
    assert d["joined_on_device"] == len(prompts)
    assert not srv._first_pending
