"""The page format has one owner, `ops/paged_attention.py`: pages taken out
of the pools and put back, a stash handle built from the cache's own
description of a page, and the description a P/D shipment's two ends compare,
in both layouts (head-major k and v; token-major k and v with an indexer's
pool)."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.paged_attention import (PagedKVCache, gather_pages,
                                         page_layout, scatter_pages)

PS = 8
LAYOUTS = {"head_major": dict(index_dim=0, preset="tiny"),
           "token_major_with_indexer": dict(index_dim=16, preset="keye_tiny")}


def _cache(index_dim, seed=0):
    """A tiny cache whose every pool holds random bytes."""
    cache = PagedKVCache.init(2, 2, 16, 12, PS, 2, 4, dtype=jnp.bfloat16,
                              index_dim=index_dim)
    rng = np.random.default_rng(seed)
    return cache.with_pools([
        jnp.asarray(rng.normal(size=p.shape).astype(np.float32)).astype(p.dtype)
        for p in cache.pools()])


def _bytes(pools):
    return [np.asarray(p).tobytes() for p in pools]


def check_gather_scatter(index_dim, preset, monkeypatch):
    """Pages out and back in, in both forms and under jit with the cache
    donated, are the same bytes in every pool, and no other page moves."""
    src, idx = _cache(index_dim), np.array([5, 2, 9], np.int32)
    layout = page_layout(src)
    assert len(layout) == len(src.pools()) == (3 if index_dim else 2)
    for page_major in (True, False):
        out = gather_pages(src, idx, page_major=page_major)
        for block, spec, pool in zip(out, layout, src.pools()):
            n_at = 0 if page_major else spec["axis"]
            assert block.shape[n_at] == len(idx) and block.dtype == pool.dtype
            assert list(np.delete(block.shape, n_at)) == spec["shape"]
            assert spec["dtype"] == str(pool.dtype)
        dst = _cache(index_dim, seed=1)
        untouched = np.setdiff1d(np.arange(12), idx)
        want_rest = _bytes(gather_pages(dst, untouched))
        put = jax.jit(scatter_pages, static_argnums=(3,), donate_argnums=(0,))
        dst = put(dst, idx, out, page_major)
        assert _bytes(gather_pages(dst, idx)) == _bytes(gather_pages(src, idx))
        assert _bytes(gather_pages(dst, untouched)) == want_rest
    with pytest.raises(ValueError, match="arrays a page"):
        scatter_pages(src, idx, out[:1])


def check_stash_handle(index_dim, preset, monkeypatch):
    """A handle built from the cache's description of a page takes what
    `gather_pages` gives and returns it, byte for byte."""
    from ray_tpu.serve.kv_transfer import KVPageStash
    monkeypatch.delenv("RAY_TPU_ARENA", raising=False)
    cache, idx = _cache(index_dim), np.array([3, 7, 0, 0], np.int32)
    layout = page_layout(cache)
    blocks = gather_pages(cache, idx)
    stash = KVPageStash(budget_bytes=1 << 20)
    try:
        handles = [stash.new_handle(layout) for _ in range(2)]
        assert handles[0]["blocks"] == layout
        assert handles[0]["nbytes"] == sum(b[0].nbytes for b in blocks)
        assert stash.put(handles, *blocks).result(60) == [None, None]
        for row, handle in enumerate(handles):
            got = stash.get(handle)
            assert len(got) == len(layout)
            for g, block in zip(got, blocks):
                assert g.dtype == block.dtype and g.shape == block.shape[1:]
                assert g.tobytes() == np.asarray(block[row]).tobytes()
    finally:
        stash.close()


def check_pd_header(index_dim, preset, monkeypatch):
    """What a PrefillServer advertises of its pages is what a DecodeServer
    of the same config compares with, and is the cache's own description."""
    from ray_tpu.serve.llm import LLMConfig
    from ray_tpu.serve.pd import PDServer, PrefillServer
    cfg = dict(preset=preset, paged=True, page_size=PS, max_seq_len=64,
               max_batch_slots=2, prefill_chunk=16, prefix_cache=False,
               seed=0)
    prefill = PrefillServer(LLMConfig(**cfg))
    decode = PDServer(LLMConfig(**cfg), params=prefill.params,
                      prefill=prefill)
    assert (prefill.cache.idx_pages is not None) == bool(index_dim)
    prompt = list(range(3, 24))

    async def drive():
        header = await prefill.prefill_begin(prompt)
        await prefill.prefill_drop(header["ship_id"])
        # the decode side raises on a layout that is not its own
        return header, await decode.generate(prompt, max_tokens=3)

    header, out = asyncio.run(asyncio.wait_for(drive(), 300))
    assert header["layout"] == page_layout(decode.cache)
    assert header["layout"] == page_layout(prefill.cache)
    assert len(out["tokens"]) == 3


CHECKS = {"gather_scatter": check_gather_scatter,
          "stash_handle": check_stash_handle,
          "pd_header": check_pd_header}


@pytest.mark.parametrize("check", list(CHECKS))
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_page_format_has_one_owner(layout, check, monkeypatch):
    CHECKS[check](monkeypatch=monkeypatch, **LAYOUTS[layout])
