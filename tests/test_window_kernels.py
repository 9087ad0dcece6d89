"""The window forms of the two attention kernels and of the page copy, in
interpret mode on the CPU, against `mha_reference` under an explicit band
mask: `flash_continuation(window=)` (the key grid starts at the window's first
block, the trailing edge is masked, what lies before the window may be
poison), `paged_attention(window=)` (the walk has a first block a row as well
as a last) and `row_pages(first=, n_pages=)`. Without `window` each is what
it was: the other files test that."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import (apply_rope, blockwise_prefill_attention,
                                   decode_attention, mha_reference)
from ray_tpu.ops.paged_attention import (PagedKVCache, _blocks_in_use,
                                         paged_attention,
                                         paged_attention_reference, row_pages)

fa = importlib.import_module("ray_tpu.ops.flash_attention")

KV_HEADS = 2
CAPACITY = 320          # ten key blocks of 32


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(fa, "_CONT_ROWS", 64)
    monkeypatch.setattr(fa, "_CONT_BLOCK_KV", 32)


def band(q_pos, n_keys, window):
    """[1, T, S] True where the query at q_pos[t] sees key s."""
    cols = jnp.arange(n_keys)[None, :]
    return ((cols <= q_pos[:, None]) & (cols > q_pos[:, None] - window))[None]


def _close(got, want, dtype):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-5 if dtype == jnp.float32
                               else 2e-2)


# (chunk, start, window, group, head size, type): windows that are and are not
# whole key blocks, starts inside and on a block's edge, a chunk still inside
# its window (nothing to skip) and chunks several windows in
CONT_CASES = [
    (64, 37, 48, 4, 64, jnp.float32), (64, 64, 64, 8, 128, jnp.float32),
    (64, 200, 48, 1, 64, jnp.float32), (64, 256, 40, 4, 128, jnp.bfloat16),
    (16, 5, 64, 8, 64, jnp.float32), (16, 250, 33, 8, 128, jnp.float32),
    (256, 64, 100, 4, 64, jnp.float32), (256, 33, 64, 8, 64, jnp.bfloat16),
    (64, 130, 31, 16, 64, jnp.float32),
]


@pytest.mark.parametrize(
    "t,start,window,g,d,dtype", CONT_CASES,
    ids=[f"T{t}-start{s}-W{w}-G{g}-D{d}-{jnp.dtype(dt).name}"
         for t, s, w, g, d, dt in CONT_CASES])
def test_continuation_with_a_window_is_the_masked_reference(t, start, window,
                                                            g, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(t + start + window), 3)
    q = jax.random.normal(ks[0], (1, t, KV_HEADS * g, d), dtype)
    k = jax.random.normal(ks[1], (1, CAPACITY, KV_HEADS, d), dtype)
    v = jax.random.normal(ks[2], (1, CAPACITY, KV_HEADS, d), dtype)
    q_pos = start + jnp.arange(t)
    want = mha_reference(q, k, v, causal=False,
                         mask=band(q_pos, CAPACITY, window))
    # whatever lies in a key block WHOLLY before the first query's window is
    # never copied: poison it, and what lies past the chunk's end
    first_block = max(0, start - window + 1) // 32 * 32
    poison = (jnp.arange(CAPACITY) < first_block) | (
        jnp.arange(CAPACITY) >= start + t)
    kp, vp = (jnp.where(poison[None, :, None, None], jnp.nan, x).swapaxes(1, 2)
              for x in (k, v))
    got = fa.flash_continuation(q, kp, vp, jnp.array([start], jnp.int32),
                                interpret=True, window=window)
    _close(got, want, dtype)
    # and the XLA forms take the same mask
    at = jnp.array([start], jnp.int32)
    _close(decode_attention(q, k, v, at, window=window), want, dtype)
    _close(blockwise_prefill_attention(q, k, v, at, key_block=32,
                                       window=window), want, dtype)


def _pool(lens, page, d, g, seed=0, pool_pages=64):
    """A pool with each row's pages scattered, its table and the dense keys."""
    rng = np.random.default_rng(seed)
    b, mp = len(lens), -(-max(lens) // page) + 2
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, KV_HEADS * g, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, mp * page, KV_HEADS, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, mp * page, KV_HEADS, d), jnp.float32)
    ids = rng.permutation(np.arange(1, pool_pages))[:b * mp].reshape(b, mp)
    kp = np.zeros((2, KV_HEADS, pool_pages, page, d), np.float32)
    vp = np.zeros_like(kp)
    for r in range(b):
        for p in range(mp):
            kp[1, :, ids[r, p]] = np.asarray(
                k[r, p * page:(p + 1) * page]).swapaxes(0, 1)
            vp[1, :, ids[r, p]] = np.asarray(
                v[r, p * page:(p + 1) * page]).swapaxes(0, 1)
    return q, k, v, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(ids, jnp.int32)


DECODE_CASES = [(4, 1), (4, 4), (16, 8), (8, 16)]   # (window pages, group)


@pytest.mark.parametrize("window_pages,g", DECODE_CASES)
def test_decode_walk_with_a_first_block_is_the_masked_reference(window_pages, g):
    page, d = 8, 64
    window = window_pages * page - 3            # not a whole page
    lens = [1, 5, window - 1, window, window + 1, 3 * window + 2, 200]
    q, k, v, kp, vp, tbl = _pool(lens, page, d, g, pool_pages=400)
    lengths = jnp.asarray(lens, jnp.int32)
    want = jnp.concatenate([
        mha_reference(q[r][None, None], k[r][None], v[r][None], causal=False,
                      mask=band(jnp.array([n - 1]), k.shape[1], window))[0]
        for r, n in enumerate(lens)])
    ref = paged_attention_reference(q, kp, vp, 1, tbl, lengths, window=window)
    _close(ref, want, jnp.float32)
    # a released page's table entry is stale: it may name any page at all
    first_page = np.maximum(np.asarray(lens) - window, 0) // page
    stale = np.asarray(tbl).copy()
    for r, fp in enumerate(first_page):
        stale[r, :fp] = 7
    got = paged_attention(q, kp, vp, 1, jnp.asarray(stale), lengths,
                          interpret=True, window=window)
    _close(got, want, jnp.float32)


def test_the_walk_steps_only_through_the_windows_blocks():
    lengths = jnp.array([5, 100, 64, 33, 0], jnp.int32)
    count, row, blk = _blocks_in_use(lengths, 128, 16, 8, window=40)
    steps = [(int(r), int(b)) for r, b in zip(row[:int(count[0])],
                                               blk[:int(count[0])])]
    # keys 60..99 of row 1 are blocks 3..6; 24..63 of row 2 are 1..3; row 3's
    # 33 keys are under the window; a free slot reads as one key
    assert steps == [(0, 0), (1, 3), (1, 4), (1, 5), (1, 6), (2, 1), (2, 2),
                     (2, 3), (3, 0), (3, 1), (3, 2), (4, 0)]
    whole, _, _ = _blocks_in_use(lengths, 128, 16, 8)
    assert int(whole[0]) == 1 + 7 + 4 + 3 + 1


def test_row_pages_copies_the_windows_pages_to_their_places():
    page, d = 8, 64
    _, k, v, kp, vp, tbl = _pool([200, 90], page, d, 1, pool_pages=128)
    cache = PagedKVCache(k_pages=kp, v_pages=vp, block_tables=tbl,
                         lengths=jnp.zeros((2,), jnp.int32))
    first, n = jnp.array([10, 3], jnp.int32), 6
    got_k, got_v = row_pages(cache, 1, interpret=True, first=first, n_pages=n)
    all_k, all_v = row_pages(cache, 1, interpret=True)
    for r, f in enumerate([10, 3]):
        np.testing.assert_array_equal(got_k[r, :, f:f + n], all_k[r, :, f:f + n])
        np.testing.assert_array_equal(got_v[r, :, f:f + n], all_v[r, :, f:f + n])


def test_interleaved_rotary_turns_neighbouring_pairs():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 3, 16), jnp.float32)
    pos = jnp.arange(10).reshape(2, 5) * 7
    got = apply_rope(x, pos, 50000.0, interleaved=True)
    # the same rotation as rotate-half on the de-interleaved head
    halves = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    turned = apply_rope(halves, pos, 50000.0)
    want = jnp.stack([turned[..., :8], turned[..., 8:]], -1).reshape(x.shape)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
