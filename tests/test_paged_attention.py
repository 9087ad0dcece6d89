"""Paged attention: pallas kernel (interpret mode) == XLA reference ==
dense decode attention; page pool write/read round-trip; allocator
bookkeeping. (Ref contrast: vLLM PagedAttention CUDA kernel tests.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import decode_attention
from ray_tpu.ops.paged_attention import (PagedKVCache, paged_attention,
                                         paged_attention_reference,
                                         write_tokens)
from ray_tpu.serve.radix_cache import PageManager


def _random_paged(b, kh, g, d, page, max_pages, lengths, seed=0, layers=1):
    """Build a stacked pool + tables where each row's pages hold random K/V."""
    rng = np.random.default_rng(seed)
    pool = b * max_pages + 1
    k_pages = rng.normal(size=(layers, kh, pool, page, d)).astype(np.float32)
    v_pages = rng.normal(size=(layers, kh, pool, page, d)).astype(np.float32)
    # deliberately scrambled page assignment (fragmentation)
    perm = rng.permutation(np.arange(1, pool))
    tables = np.zeros((b, max_pages), np.int32)
    used = 0
    for i in range(b):
        need = -(-lengths[i] // page)
        tables[i, :need] = perm[used:used + need]
        used += need
    q = rng.normal(size=(b, kh * g, d)).astype(np.float32)
    return (jnp.array(q), jnp.array(k_pages), jnp.array(v_pages),
            jnp.array(tables), jnp.array(lengths, dtype=jnp.int32))


@pytest.mark.parametrize("g", [1, 4])
def test_kernel_matches_reference_fragmented(g):
    b, kh, d, page, max_pages = 3, 2, 64, 8, 4
    lengths = np.array([1, 13, 32])
    q, kp, vp, tbl, lens = _random_paged(b, kh, g, d, page, max_pages, lengths)
    out_k = paged_attention(q, kp, vp, 0, tbl, lens, interpret=True)
    out_r = paged_attention_reference(q, kp, vp, 0, tbl, lens)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-5, rtol=2e-5)


def test_reference_matches_dense_decode():
    """Contiguous pages == the model's dense decode_attention oracle."""
    b, kh, g, d, page, max_pages = 2, 2, 1, 32, 4, 8
    s_max = page * max_pages
    rng = np.random.default_rng(1)
    lengths = np.array([5, 29])
    k_cache = rng.normal(size=(b, s_max, kh, d)).astype(np.float32)
    v_cache = rng.normal(size=(b, s_max, kh, d)).astype(np.float32)
    q = rng.normal(size=(b, kh * g, d)).astype(np.float32)

    # lay the same cache out as contiguous per-row pages
    pool = b * max_pages + 1
    k_pages = np.zeros((1, kh, pool, page, d), np.float32)
    v_pages = np.zeros((1, kh, pool, page, d), np.float32)
    tables = np.zeros((b, max_pages), np.int32)
    nxt = 1
    for i in range(b):
        for p in range(max_pages):
            k_pages[0, :, nxt] = k_cache[i, p * page:(p + 1) * page].transpose(1, 0, 2)
            v_pages[0, :, nxt] = v_cache[i, p * page:(p + 1) * page].transpose(1, 0, 2)
            tables[i, p] = nxt
            nxt += 1

    out_p = paged_attention_reference(
        jnp.array(q), jnp.array(k_pages), jnp.array(v_pages), 0,
        jnp.array(tables), jnp.array(lengths, dtype=jnp.int32))
    # decode_attention takes tokens-BEFORE-the-chunk and attends <= L;
    # paged lengths are inclusive counts, hence the -1
    out_d = decode_attention(
        jnp.array(q)[:, None], jnp.array(k_cache), jnp.array(v_cache),
        jnp.array(lengths - 1, dtype=jnp.int32))
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_d)[:, 0],
                               atol=2e-5, rtol=2e-5)


def test_write_tokens_roundtrip():
    l, b, kh, d, page = 2, 2, 2, 8, 4
    cache = PagedKVCache.init(l, kh, d, num_pages=16, page_size=page,
                              batch_slots=b, max_pages_per_seq=3,
                              dtype=jnp.float32)
    mgr = PageManager(16, page, b, 3)
    rows = [mgr.allocate(0, 6), mgr.allocate(1, 3)]
    cache = cache.replace(block_tables=jnp.array(rows, jnp.int32))

    rng = np.random.default_rng(2)
    # prefill: row 0 writes 6 tokens, row 1 writes 3; row 1's positions 3-5
    # are padding that lands on reserved page 0 (table entry 0) harmlessly
    k_new = rng.normal(size=(l, b, 6, kh, d)).astype(np.float32)
    v_new = rng.normal(size=(l, b, 6, kh, d)).astype(np.float32)
    positions = np.stack([np.arange(6), np.arange(6)])
    cache = write_tokens(cache, jnp.array(k_new), jnp.array(v_new),
                         jnp.array(positions, dtype=jnp.int32))

    # read back through the tables: row 0 position 5 -> page 5//4=1, off 1
    tbl = np.array(cache.block_tables)
    got = np.asarray(cache.k_pages)[0, :, tbl[0, 5 // page], 5 % page]
    np.testing.assert_allclose(got, k_new[0, 0, 5])
    got1 = np.asarray(cache.v_pages)[1, :, tbl[1, 0], 2]
    np.testing.assert_allclose(got1, v_new[1, 1, 2])


def test_page_manager_alloc_extend_free():
    mgr = PageManager(num_pages=8, page_size=4, batch_slots=2,
                      max_pages_per_seq=4)
    assert mgr.can_fit(16) and not mgr.can_fit(100)
    row = mgr.allocate(0, 5)  # 2 pages
    assert len([p for p in row if p]) == 2 and mgr.pages_in_use == 2
    row = mgr.extend(0, 9)    # 3rd page
    assert len([p for p in row if p]) == 3
    row2 = mgr.allocate(1, 16)  # 4 pages
    assert mgr.pages_in_use == 7
    with pytest.raises(MemoryError):
        mgr.extend(1, 17)  # pool exhausted (only page 0 reserved left)
    mgr.free(0)
    assert mgr.pages_in_use == 4
    mgr.free(1)
    assert mgr.pages_in_use == 0


def test_model_paged_decode_matches_dense():
    """Greedy generation through the Llama decode path must be identical
    with the paged cache and the dense KVCache (same params, same prompt)."""
    from ray_tpu.models.llama import KVCache, Llama, LlamaConfig

    cfg = LlamaConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32,
                           max_seq_len=32)
    model = Llama(cfg)
    prompt = jnp.array([[3, 1, 4, 1, 5, 9, 2, 6, 5]], jnp.int32)
    P, steps = prompt.shape[1], 6
    params = model.init(jax.random.PRNGKey(0), prompt)

    def greedy_dense():
        cache = KVCache.init(cfg, 1, cfg.max_seq_len)
        logits, cache = model.apply(params, prompt, cache=cache)
        toks = [int(jnp.argmax(logits[0, -1]))]
        for _ in range(steps - 1):
            logits, cache = model.apply(
                params, jnp.array([[toks[-1]]], jnp.int32), cache=cache)
            toks.append(int(jnp.argmax(logits[0, -1])))
        return toks

    def greedy_paged():
        page = 4
        mgr = PageManager(num_pages=16, page_size=page, batch_slots=1,
                          max_pages_per_seq=8)
        row = mgr.allocate(0, P + steps)
        cache = PagedKVCache.init(
            cfg.n_layers, cfg.n_kv_heads, cfg.head_dim, num_pages=16,
            page_size=page, batch_slots=1, max_pages_per_seq=8,
            dtype=jnp.float32)
        cache = cache.replace(block_tables=jnp.array([row], jnp.int32))
        logits, cache = model.apply(params, prompt, cache=cache)
        toks = [int(jnp.argmax(logits[0, -1]))]
        for _ in range(steps - 1):
            logits, cache = model.apply(
                params, jnp.array([[toks[-1]]], jnp.int32), cache=cache)
            toks.append(int(jnp.argmax(logits[0, -1])))
        return toks

    assert greedy_dense() == greedy_paged()
