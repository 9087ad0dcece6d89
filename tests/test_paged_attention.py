"""Paged attention: pallas kernel (interpret mode) == XLA reference ==
dense decode attention; page pool write/read round-trip; allocator
bookkeeping. (Ref contrast: vLLM PagedAttention CUDA kernel tests.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import decode_attention
from ray_tpu.ops.paged_attention import (PagedKVCache, pages_per_block,
                                         paged_attention,
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


# What the kernel's plan can get wrong: a grid step folds a block of
# `pages_per_block` pages of one row, the grid walks the blocks the rows hold
# keys in, and an operand past a row's end names what it named a step before.
# Pages of 64 tokens of 128 at 131,072 B for all kv heads, the cells' page,
# make a block 8 pages = 512 tokens. Lengths a row; `shared`: rows 0 and 1
# have their first pages in common; `layers` / `layer`: a stacked pool whose
# other layers are NaN (`traced`: the layer a traced value under `jit`);
# `pool` / `query`: the types (8 kv heads in bf16, 4 in f32); `d`: the heads'
# size (at 64 a page takes the room of 128 in VMEM: 4 pages a block).
_BLOCK = 512
_PLAN_CASES = {
    "width_5_narrower_than_a_block": dict(width=5, lengths=[1, 256, 320, 257]),
    "width_20_no_multiple_of_the_block": dict(width=20, lengths=[1280, 1, 1025,
                                                                 513]),
    "width_36_mixtrals_four_and_a_half_blocks": dict(
        width=36, lengths=[2304, 1, 2049, 1024]),
    "width_64_a_multiple_of_the_block": dict(width=64, lengths=[4096, 1, 3000,
                                                                513]),
    "rows_of_one_token": dict(width=8, lengths=[1, 1, 1]),
    "rows_that_end_on_a_blocks_edge": dict(width=24, lengths=[512, 1024,
                                                              1536]),
    "rows_that_fill_their_table": dict(width=16, lengths=[1024, 1024]),
    "rows_one_token_into_a_new_block": dict(width=24, lengths=[513, 1025, 1]),
    "inactive_rows_between_long_ones": dict(width=24, lengths=[1400, 1, 1536,
                                                               1, 600]),
    "short_rows_between_long_ones": dict(width=24, lengths=[100, 1400, 130,
                                                            65, 1536, 300]),
    "two_rows_share_their_first_pages": dict(width=24, lengths=[1200, 1300,
                                                                40],
                                             shared=12),
    "g_1": dict(width=18, lengths=[1, 600, 1152], g=1),
    "g_4": dict(width=18, lengths=[1, 600, 1152], g=4),
    "g_8": dict(width=18, lengths=[1, 600, 1152], g=8),
    "layer_0_of_a_stack_the_others_nan": dict(width=12, lengths=[70, 768, 513],
                                              layers=3, layer=0),
    "layer_2_of_a_stack_the_others_nan": dict(width=12, lengths=[70, 768, 513],
                                              layers=3, layer=2),
    "layer_1_traced_under_jit": dict(width=12, lengths=[70, 768, 513],
                                     layers=3, layer=1, traced=True),
    "bf16_pools_f32_query": dict(width=18, lengths=[1, 600, 1152, 1025], g=4,
                                 pool=jnp.bfloat16),
    "bf16_pools_bf16_query": dict(width=18, lengths=[1, 600, 1152, 1025], g=8,
                                  pool=jnp.bfloat16, query=jnp.bfloat16),
    "heads_of_64": dict(width=10, lengths=[1, 256, 257, 640, 100], d=64),
    "heads_of_64_bf16_layer_traced": dict(
        width=10, lengths=[1, 256, 257, 640, 100], d=64, g=4, layers=2,
        layer=1, traced=True, pool=jnp.bfloat16, query=jnp.bfloat16),
}


@pytest.mark.parametrize("name", list(_PLAN_CASES))
def test_kernel_walks_blocks_as_the_reference_reads_pages(name):
    case = dict(g=2, layers=1, layer=0, shared=0, pool=jnp.float32,
                query=jnp.float32, d=128, traced=False)
    case.update(_PLAN_CASES[name])
    width, lengths, g = case["width"], np.array(case["lengths"]), case["g"]
    page, d = 64, case["d"]
    kh = 131072 // (page * d * jnp.dtype(case["pool"]).itemsize)
    in_vmem = 131072 * 128 // d
    assert pages_per_block(in_vmem, 64) * page == _BLOCK * d // 128
    if name.startswith("width"):
        block = pages_per_block(in_vmem, width) * page
        assert (width * page % block != 0) == ("no_multiple" in name
                                               or "and_a_half" in name)

    rng = np.random.default_rng(len(name))
    need = -(-lengths // page)
    n_pool = int(need.sum()) + 1
    perm = rng.permutation(np.arange(1, n_pool))
    tables = np.zeros((len(lengths), width), np.int32)
    used = 0
    for i, n in enumerate(need):
        tables[i, :n] = perm[used:used + n]
        used += n
    tables[1, :case["shared"]] = tables[0, :case["shared"]]
    shape = (case["layers"], kh, n_pool, page, d)
    k, v = (rng.normal(size=shape).astype(np.float32) for _ in range(2))
    for pool in (k, v):
        pool[np.arange(case["layers"]) != case["layer"]] = np.nan
    q = rng.normal(size=(len(lengths), kh * g, d)).astype(np.float32)
    args = (jnp.array(q, case["query"]), jnp.array(k, case["pool"]),
            jnp.array(v, case["pool"]), case["layer"], jnp.array(tables),
            jnp.array(lengths, jnp.int32))

    if case["traced"]:     # one program for every layer
        got = jax.jit(lambda *a: paged_attention(*a, interpret=True))(
            *args[:3], jnp.int32(case["layer"]), *args[4:])
    else:
        got = paged_attention(*args, interpret=True)
    want = paged_attention_reference(*args)
    assert got.dtype == want.dtype == case["query"]
    # an f32 result is held to the f32 tests' tolerance whatever the pools'
    # type (the arithmetic is f32); a bf16 result to its rounding
    tol = 2e-5 if case["query"] == jnp.float32 else 8e-3
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


def test_walk_has_a_step_for_every_block_in_use_and_no_other():
    from ray_tpu.ops.paged_attention import _blocks_in_use

    # 4 rows of a table of 20 pages of 64: blocks of 512 keys, 3 a row at most
    lengths = jnp.array([1025, 0, 512, 5000], jnp.int32)
    count, row, blk = _blocks_in_use(lengths, 20 * 64, 512, 3)
    n = int(count[0])
    assert n == 3 + 1 + 1 + 3     # a free row reads as one key; 5000 > room
    assert row.shape == blk.shape == (12,)
    assert list(zip(np.asarray(row)[:n], np.asarray(blk)[:n])) == [
        (0, 0), (0, 1), (0, 2), (1, 0), (2, 0), (3, 0), (3, 1), (3, 2)]


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
