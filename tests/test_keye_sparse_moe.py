"""Keye-VL-2.0's language model at a tiny size on the CPU (ISSUE 28): q/k norm,
three-component rotary, the grouped expert product, the lightning indexer's
top-k selection in the uncached forward, the paged prefill (chunk-local and
continuation) and the paged decode (single steps and the fused chunk), the
three-pool cache through demotion, restore and the P/D hand-off, and the new
counters.

The oracle is `_reference_logits`, a test-side copy of the equations of
`perfbench/references/keye_vl2.py` (all positions at once, plain jax.numpy,
float32). Everything here runs in float32, so the program must agree with it
to rounding: 2e-4 on logits of magnitude 1 covers the different order of the
sums (the engine's key blocks and online softmax, the grouped product's
sort); a wrong selection moves logits by 1e-2 and more, as the control with
the selection left out shows.
"""

import asyncio
import time

import jax
import jax.numpy as jnp

import numpy as np
import pytest

from ray_tpu.models.llama import Llama, LlamaConfig
from ray_tpu.models.moe import MoEMLP, grouped_experts, grouped_product
from ray_tpu.ops import paged_attention
from ray_tpu.ops.paged_attention import (PagedKVCache, index_block_keys,
                                         index_keys, index_scores,
                                         kth_largest,
                                         sparse_attention_reference,
                                         sparse_decode_scores,
                                         sparse_paged_decode,
                                         sparse_paged_prefill, top_k_places,
                                         write_layer_tokens)
from ray_tpu.serve.llm import LLMConfig, LLMServer

TOL = 2e-4
PS, MAX_PAGES = 8, 24          # pages of 8, rows of up to 192 tokens


def _cfg(**kw):
    return LlamaConfig.keye_tiny(dtype=jnp.float32, param_dtype=jnp.float32,
                                 max_seq_len=256, **kw)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    m = Llama(cfg)
    params = m.init(jax.random.PRNGKey(7), jnp.zeros((1, 8), jnp.int32))
    # norm scales and the LayerNorm's bias away from 1 and 0, so that they count
    params = jax.tree_util.tree_map(
        lambda x: x + 0.1 * jax.random.normal(
            jax.random.PRNGKey(x.size), x.shape, x.dtype) if x.ndim == 1 else x,
        params)
    return cfg, m, params


# ---------------------------------------------------------------------------
# the test-side reference
# ---------------------------------------------------------------------------

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta, sections=None):
    """x [T, H, D]; pos [T] or [3, T]."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if pos.ndim == 2:
        comp = np.repeat(np.arange(len(sections)), sections)
        pos = pos[comp, :].T
    else:
        pos = pos[:, None]
    ang = pos.astype(jnp.float32) * freqs
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _reference_logits(params, tokens, cfg, positions=None, select=True):
    """[T, V] logits of one sequence. `select=False` is the control: every
    causal key attended, the selection left out."""
    p = params["params"]
    t = len(tokens)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t)[None], (3, t))
    eps, theta = cfg.norm_eps, cfg.rope_theta
    causal = jnp.tril(jnp.ones((t, t), bool))
    x = p["embed"]["embedding"][jnp.asarray(tokens)]
    for i in range(cfg.n_layers):
        lp = p[f"layers_{i}"]
        a, ix = lp["attn"], lp["attn"]["indexer"]
        h = _rms(x, lp["attn_norm"]["scale"], eps)
        q = _rms((h @ a["wq"]["kernel"]).reshape(t, cfg.n_heads, -1),
                 a["q_norm"]["scale"], eps)
        k = _rms((h @ a["wk"]["kernel"]).reshape(t, cfg.n_kv_heads, -1),
                 a["k_norm"]["scale"], eps)
        v = (h @ a["wv"]["kernel"]).reshape(t, cfg.n_kv_heads, -1)
        q = _rope(q, positions, theta, cfg.rope_sections)
        k = _rope(k, positions, theta, cfg.rope_sections)
        qi = _rope((h @ ix["wq"]["kernel"]).reshape(t, cfg.index_heads, -1),
                   positions[0], theta)
        ki = h @ ix["wk"]["kernel"]
        mu = ki.mean(-1, keepdims=True)
        ki = ((ki - mu) * jax.lax.rsqrt(((ki - mu) ** 2).mean(-1, keepdims=True)
                                        + eps)
              * ix["k_norm"]["scale"] + ix["k_norm"]["bias"])
        ki = _rope(ki[:, None], positions[0], theta)[:, 0]
        w = h @ ix["w"]["kernel"]
        index = jnp.einsum("tjs,tj->ts", jax.nn.relu(
            jnp.einsum("tjd,sd->tjs", qi, ki)), w)
        index = jnp.where(causal, index, -jnp.inf)
        keep = causal
        if select and t > cfg.index_topk:
            sel = jax.lax.top_k(index, cfg.index_topk)[1]
            keep = jnp.zeros((t, t), bool).at[
                jnp.arange(t)[:, None], sel].set(True) & causal
        g = cfg.n_heads // cfg.n_kv_heads
        s = jnp.einsum("tkgd,skd->kgts", q.reshape(t, cfg.n_kv_heads, g, -1),
                       k) / np.sqrt(cfg.head_dim)
        pr = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), -1)
        o = jnp.einsum("kgts,skd->tkgd", pr, v).reshape(t, -1)
        x = x + o @ a["wo"]["kernel"]
        h2 = _rms(x, lp["mlp_norm"]["scale"], eps)
        moe = lp["moe"]
        probs = jax.nn.softmax(h2 @ moe["router"]["kernel"], -1)
        vals, idx = jax.lax.top_k(probs, cfg.moe_top_k)
        vals = vals / vals.sum(-1, keepdims=True)
        gates = (jax.nn.one_hot(idx, cfg.n_experts) * vals[..., None]).sum(1)
        for e in range(cfg.n_experts):
            out = (jax.nn.silu(h2 @ moe["w_gate"][e]) * (h2 @ moe["w_up"][e])
                   ) @ moe["w_down"][e]
            x = x + gates[:, e:e + 1] * out
    x = _rms(x, p["final_norm"]["scale"], eps)
    return x @ p["lm_head"]["kernel"]


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", [12, 16, 17, 70])   # below, at, just past, well past topk
def test_full_forward_agrees_with_reference(model, t):
    cfg, m, params = model
    toks = _tokens(t, t)
    got, _ = m.apply(params, jnp.asarray(toks)[None])
    want = _reference_logits(params, toks, cfg)
    np.testing.assert_allclose(got[0], want, atol=TOL)


def test_three_unequal_rotary_components(model):
    cfg, m, params = model
    t = 40
    toks = _tokens(t, 3)
    pos3 = jnp.stack([jnp.arange(t), jnp.arange(t) // 3, (jnp.arange(t) * 7) % 5])
    got, _ = m.apply(params, jnp.asarray(toks)[None], positions=pos3[:, None])
    want = _reference_logits(params, toks, cfg, positions=pos3)
    np.testing.assert_allclose(got[0], want, atol=TOL)
    text = _reference_logits(params, toks, cfg)
    assert float(jnp.abs(want - text).max()) > 1e-2    # the components count
    # all three equal is one-component rotary exactly
    same, _ = m.apply(params, jnp.asarray(toks)[None], positions=jnp.broadcast_to(
        jnp.arange(t)[None, None], (3, 1, t)))
    plain, _ = m.apply(params, jnp.asarray(toks)[None])
    np.testing.assert_array_equal(same, plain)


def _row_cache(cfg, n_rows=1):
    cache = PagedKVCache.init(cfg.n_layers, cfg.n_kv_heads, cfg.head_dim,
                              n_rows * MAX_PAGES + 1, PS, n_rows, MAX_PAGES,
                              dtype=jnp.float32, index_dim=cfg.index_dim)
    tables = 1 + jnp.arange(n_rows * MAX_PAGES).reshape(n_rows, MAX_PAGES)
    return cache.replace(block_tables=tables.astype(jnp.int32))


# (first chunk, continuation chunks, decode steps): contexts below, at and
# well above index_topk = 16 in each path
@pytest.mark.parametrize("first,more,n_decode", [
    (8, (), 6),              # decode below topk, all keys selected
    (12, (4,), 12),          # continuation ends at topk; decode crosses it
    (40, (), 4),             # a chunk-local first chunk longer than topk
    (16, (24, 30), 20),      # continuation chunks and decode well above topk
])
def test_paged_prefill_then_decode_agrees_with_reference(model, first, more,
                                                         n_decode):
    cfg, m, params = model
    total = first + sum(more) + n_decode
    toks = jnp.asarray(_tokens(total, total))[None]
    want = _reference_logits(params, toks[0].tolist(), cfg)
    row = _row_cache(cfg)
    got, pos = [], 0
    for n, local in [(first, True)] + [(c, False) for c in more]:
        logits, row = m.apply(params, toks[:, pos:pos + n], cache=row,
                              paged_chunk_local=local)
        got.append(logits[0])
        pos += n
    for i in range(pos, total):
        logits, row = m.apply(params, toks[:, i:i + 1], cache=row)
        got.append(logits[0])
    np.testing.assert_allclose(jnp.concatenate(got), want, atol=TOL)
    assert int(row.lengths[0]) == total


KV_HEADS = 2
PAGE = 8


def _selecting_row(n, g, d, dtype, dead=(), live=None, seed=3):
    """A row of `n` tokens in a token-major cache of three pools under a
    scrambled table, and the tensors it was made of. The indexer's scores are
    made to order: its queries, keys and weights are positive, so every score
    is positive and distinct, but a key in a 32-key block of `dead`, or (with
    `live`) not among every `live`-th, has all its products negative: the
    relu is shut and it scores exactly 0."""
    j, di = 2, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (1, n, KV_HEADS * g, d), dtype)
    k = jax.random.normal(ks[1], (1, n, KV_HEADS, d), dtype)
    v = jax.random.normal(ks[2], (1, n, KV_HEADS, d), dtype)
    qi = jnp.abs(jax.random.normal(ks[3], (1, n, j, di), dtype)) + 0.1
    ki = jnp.abs(jax.random.normal(ks[4], (1, n, di), dtype)) + 0.1
    wi = jnp.abs(jax.random.normal(ks[5], (1, n, j), dtype)) + 0.1
    at = np.arange(n)
    shut = np.isin(at // 32, dead)
    if live:
        shut |= at % live != 0
    ki = jnp.where(jnp.asarray(shut)[None, :, None], -ki, ki)

    width = -(-n // PAGE) + 2                       # two placeholder entries
    held = -(-n // PAGE)
    table = np.zeros((1, width), np.int32)
    table[0, :held] = 1 + np.random.default_rng(seed).permutation(held)
    pad = held * PAGE - n

    def pool(x, layers=2):
        """[1, n, ...] by position -> [layers, pages, PAGE, ...], layer 1."""
        x = jnp.pad(x[0], ((0, pad),) + ((0, 0),) * (x.ndim - 2))
        pages = x.reshape((held, PAGE) + x.shape[1:])
        out = jnp.zeros((layers, 1 + held) + pages.shape[1:], dtype)
        return out.at[1, table[0, :held]].set(pages)

    cache = PagedKVCache(k_pages=pool(k), v_pages=pool(v),
                         idx_pages=pool(ki).reshape(2, 1 + held, 1, PAGE * di),
                         block_tables=jnp.asarray(table),
                         lengths=jnp.array([n], jnp.int32))
    return cache, (q, k, v, qi, ki, wi)


# (t, start, g, topk, made-to-order scores)
SELECTED = {
    "row_shorter_than_topk": (16, 16, 4, 64, {}),
    "no_key_of_the_first_block": (64, 96, 4, 16, {"dead": (0,)}),
    "no_key_of_a_middle_block": (64, 96, 4, 16, {"dead": (2,)}),
    "no_key_of_the_chunks_own_blocks": (64, 96, 4, 16, {"dead": (3, 4)}),
    # 7 keys score above 0 at most, so 9 and more of the 16 are taken from
    # those tied at 0: the earliest positions, ranked under the `cond`
    "tied_at_0_across_the_kth_place": (32, 64, 4, 16, {"live": 16}),
    "G8_bucket_64": (64, 128, 8, 16, {}),
    "G8_bucket_128": (128, 64, 8, 16, {"dead": (1,)}),
    "G8_bucket_512": (512, 64, 8, 16, {}),
    "G8_bfloat16": (64, 100, 8, 16, {"dtype": jnp.bfloat16}),
    "start_inside_a_key_block": (32, 77, 4, 16, {}),
    "start_inside_a_key_block_G1": (16, 45, 1, 16, {"live": 3}),
}


@pytest.mark.parametrize("case", sorted(SELECTED))
def test_selected_prefill_is_the_reference(case):
    """`sparse_paged_prefill` on a cache against the uncached reference
    (`lax.top_k`'s own set: ties to the earlier position) on the row's
    tensors: the same output, finite, whether or not a query has more keys
    tied at its k-th value than room (the rank's `cond`, both sides), a row
    shorter than `topk`, and a key block no query keeps a key of."""
    t, start, g, topk, made = SELECTED[case]
    made = dict(made)
    dtype = made.pop("dtype", jnp.float32)
    n = start + t
    cache, (q, k, v, qi, ki, wi) = _selecting_row(n, g, 32, dtype, **made)
    chunk = slice(start, n)
    got = jax.jit(sparse_paged_prefill, static_argnums=(4, 6),
                  static_argnames="key_block")(
        q[:, chunk], qi[:, chunk], wi[:, chunk], cache, 1,
        jnp.arange(start, n)[None], topk, key_block=32)
    want = sparse_attention_reference(q, k, v, qi, ki, wi, topk)[:, chunk]
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-5 if dtype == jnp.float32
                               else 2e-2)


# the decode step's selection (ISSUE 48): a row of the first of SELECTED's
# kinds, its last token as the decode query
DECODED = {
    "row_shorter_than_topk": (40, 4, 64, {}),
    "row_of_exactly_topk": (64, 4, 64, {}),
    "no_key_of_a_middle_block": (160, 4, 16, {"dead": (2,)}),
    "tied_at_0_across_the_kth_place": (96, 4, 16, {"live": 16}),
    "G8_bfloat16": (164, 8, 16, {"dtype": jnp.bfloat16}),
    "G1_inside_a_page": (45, 1, 16, {"live": 3}),
}


@pytest.mark.parametrize("case", sorted(DECODED))
def test_selected_decode_is_the_reference(case):
    """`sparse_paged_decode` of a row's last token on a cache (the kernel
    interpreted, the positions by value) against the uncached reference's
    last row: the same output whether the row holds fewer keys than `topk`,
    exactly as many, keys tied at 0 across the k-th place, or a block none
    is kept of."""
    n, g, topk, made = DECODED[case]
    made = dict(made)
    dtype = made.pop("dtype", jnp.float32)
    cache, (q, k, v, qi, ki, wi) = _selecting_row(n, g, 32, dtype, **made)
    got = jax.jit(sparse_paged_decode, static_argnums=(4, 6))(
        q[:, -1], qi[:, -1], wi[:, -1], cache, 1, cache.lengths, topk)
    want = sparse_attention_reference(q, k, v, qi, ki, wi, topk)[:, -1]
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-5 if dtype == jnp.float32
                               else 2e-2)


# a table of 40 pages of 8: rows of 0 (a free slot), 1, under a page, on a
# page's edge, on a block's edge (4 pages a block, and the kernel's own 32),
# the table's full width; each between two rows that hold keys and, once
# more, after a free slot, so that a row's first copies are started by the
# row before it and by itself
RAGGED = {"free_slot": 0, "one_key": 1, "under_a_page": 5, "a_pages_edge": 8,
          "a_block_of_4s_edge": 32, "past_a_block_of_4s_edge": 33,
          "a_block_of_32s_edge": 256, "the_tables_width": 320}


@pytest.mark.parametrize("index_dim,dtype", [(8, jnp.float32),
                                             (32, jnp.float32),
                                             (32, jnp.bfloat16)])
@pytest.mark.parametrize("pages_a_block", [4, 32])
@pytest.mark.parametrize("case", sorted(RAGGED))
def test_decode_scores_are_the_copied_keys_scores(monkeypatch, case,
                                                  pages_a_block, index_dim,
                                                  dtype):
    """The kernel `sparse_decode_scores` (interpreted) on the pool where it
    lies against `index_scores` over `index_keys`, the copy it does without,
    under the length mask: position for position, -inf at and past a row's
    length, whatever the pool held there (NaN here). Eight tokens of 8
    values to a row of the pool, and two rows of four of 32."""
    monkeypatch.setattr(paged_attention, "_INDEX_PAGES_PER_BLOCK",
                        pages_a_block)
    monkeypatch.setattr(paged_attention, "_INDEX_COPY_STRETCHES", (8, 2, 1))
    mp, heads, layers = 40, 2, 2
    lengths = np.array([77, RAGGED[case], 300, 0, RAGGED[case], 9], np.int32)
    rows = len(lengths)
    r = paged_attention.index_pack(PAGE, index_dim)
    ks = jax.random.split(jax.random.PRNGKey(RAGGED[case]), 3)
    n_pages = 1 + rows * mp
    pool = jax.random.normal(ks[0], (layers, n_pages, PAGE // r, r * index_dim),
                             dtype)
    table = np.zeros((rows, mp), np.int32)
    free = 1 + np.random.default_rng(3).permutation(n_pages - 1)
    for b, n in enumerate(-(-lengths // PAGE)):
        table[b, :n] = free[b * mp:b * mp + n]
    # a page no row holds keys on must not be read: NaN would show
    held = np.zeros(n_pages, bool)
    held[table[table > 0]] = True
    pool = jnp.where(jnp.asarray(held)[None, :, None, None], pool, jnp.nan)
    cache = PagedKVCache(
        k_pages=jnp.zeros((layers, 1, PAGE, 1, 8), dtype),
        v_pages=jnp.zeros((layers, 1, PAGE, 1, 8), dtype), idx_pages=pool,
        block_tables=jnp.asarray(table), lengths=jnp.asarray(lengths))
    qi = jax.random.normal(ks[1], (rows, heads, index_dim), dtype)
    wi = jax.random.normal(ks[2], (rows, heads), dtype)
    got = np.asarray(jax.jit(sparse_decode_scores, static_argnums=3)(
        qi, wi, cache, 1, cache.lengths))
    assert got.shape == (rows, mp * PAGE) and got.dtype == np.float32
    valid = np.arange(mp * PAGE)[None] < lengths[:, None]
    assert (got[~valid] == -np.inf).all() and np.isfinite(got[valid]).all()
    ki = index_keys(cache, 1, cache.block_tables)
    want = np.asarray(index_scores(qi[:, None], wi[:, None], ki)[:, 0])
    np.testing.assert_allclose(got[valid], want[valid], rtol=1e-5, atol=1e-5)
    assert index_block_keys(PAGE, mp) == min(pages_a_block, mp) * PAGE


def _scores_to_order(case):
    """[rows, 96] scores, `k` = 16, the rows' lengths in them as -inf: what
    `lax.top_k` makes of each row is the test's oracle."""
    rng = np.random.default_rng(len(case))
    s_max, k = 96, 16
    scores = rng.permutation(4 * s_max).reshape(4, s_max).astype(np.float32)
    lengths = np.array([96, 96, 50, 96])
    if case == "distinct":
        pass
    elif case == "ties_at_the_kth_value":
        # 10 above, then 20 tied at the k-th value all over the row: the
        # six earliest of them are kept
        scores[:] = -1.0
        for row in scores:
            row[rng.choice(s_max, 30, replace=False)] = 5.0
            row[rng.choice(np.flatnonzero(row == 5.0), 10, replace=False)] = 9.0
    elif case == "all_tied":
        scores[:] = 0.0
    elif case == "fewer_than_k_keys":
        lengths = np.array([0, 1, 7, 15])
    elif case == "exactly_k_keys":
        lengths = np.array([16, 16, 16, 16])
    elif case == "minus_inf_inside_the_row":
        # 40 keys, 30 of them -inf: 10 are kept, no -inf among them
        lengths = np.array([40, 40, 40, 96])
        for row in scores[:3]:
            row[rng.choice(40, 30, replace=False)] = -np.inf
        scores[3, rng.choice(96, 60, replace=False)] = -np.inf
    elif case == "ties_at_the_kth_value_and_minus_inf":
        scores[:] = 2.0
        scores[:, ::3] = -np.inf
        scores[:, 1::6] = 7.0
    else:
        raise KeyError(case)
    scores = np.where(np.arange(s_max)[None] < lengths[:, None], scores,
                      -np.inf)
    return scores, k


@pytest.mark.parametrize("page", [8, 32])     # a byte of kept bits, and four
@pytest.mark.parametrize("case", [
    "distinct", "ties_at_the_kth_value", "all_tied", "fewer_than_k_keys",
    "exactly_k_keys", "minus_inf_inside_the_row",
    "ties_at_the_kth_value_and_minus_inf"])
def test_places_by_value_are_top_ks_set(case, page):
    """`top_k_places` against `lax.top_k` on scores made to order: the same
    SET of positions a row (of the keys tied at the k-th value the earliest;
    `lax.top_k`'s order of equal values is by position too), each at its
    page of a scrambled table and its offset; a -inf is never a key; a row
    of fewer than k keys keeps them all and flags the rest of its slots,
    which name page 0 and are given no weight."""
    scores, k = _scores_to_order(case)
    rows, s_max = scores.shape
    mp = s_max // page
    table = 1 + np.random.default_rng(1).permutation(rows * mp).reshape(
        rows, mp).astype(np.int32)
    page_ids, offsets, chosen = (np.asarray(x) for x in top_k_places(
        jnp.asarray(scores), jnp.asarray(table), k, page))
    top, sel = (np.asarray(x) for x in jax.lax.top_k(jnp.asarray(scores), k))
    for b in range(rows):
        want = sorted(sel[b][top[b] > -np.inf])
        assert chosen[b].sum() == len(want)
        assert chosen[b, :len(want)].all()       # the flagged slots are last
        got = [int(np.flatnonzero(table[b] == p)[0]) * page + o
               for p, o in zip(page_ids[b][chosen[b]], offsets[b][chosen[b]])]
        assert got == want                       # by position, none twice
        assert (page_ids[b][~chosen[b]] == 0).all()
        assert (offsets[b][~chosen[b]] == 0).all()


def test_the_made_to_order_scores_do_what_the_cases_say():
    """The cases above rest on it: with a block's keys shut no query selects
    one of them, and with one key in 16 open the rest of the selection is the
    earliest of the keys tied at 0."""
    def selection(n, topk, **made):
        _, (_, _, _, qi, ki, wi) = _selecting_row(n, 4, 32, jnp.float32, **made)
        scores = jnp.where(jnp.tril(jnp.ones((n, n), bool)),
                           index_scores(qi, wi, ki)[0], -jnp.inf)
        return np.asarray(jax.lax.top_k(scores, topk)[1]), np.asarray(scores)

    sel, _ = selection(160, 16, dead=(2,))
    assert not np.isin(sel[96:] // 32, [2]).any()
    sel, scores = selection(96, 16, live=16)
    for query in (64, 95):
        open_keys = [s for s in range(query + 1) if s % 16 == 0]
        assert len(open_keys) < 16
        assert sorted(sel[query]) == sorted(
            open_keys + [s for s in range(96) if s % 16][:16 - len(open_keys)])
        assert (scores[query, :query + 1] == 0).sum() > 16


def test_engine_prefills_a_prompt_in_chunks_to_the_rows_end(model, loop):
    """50 tokens in chunks of 16 into a row of 60: the first chunk is local
    (no more than `index_topk` tokens from position 0), the next two select
    over the row's pages, and so does the last, 2 tokens in a bucket of 16
    clamped to the 12 the row has left; the logprobs are the uncached
    forward's."""
    cfg, _, params = model
    srv = LLMServer(_llm_cfg(num_pages=40, prefill_chunk=16, max_seq_len=60),
                    params=params)
    try:
        prompt = _tokens(50, 50)
        out = _generate(loop, srv, prompt, 6, logprobs=True)
        d = srv.stats()["decode"]
    finally:
        srv.close()
    assert (d["prefill_chunks"], d["continuation_chunks"]) == (4, 3)
    assert d["prefill_padded_tokens"] == 16 * 3 + 12
    seq = prompt + out["tokens"]
    logp = jax.nn.log_softmax(_reference_logits(params, seq[:-1], cfg), -1)
    want = [float(logp[49 + i, t]) for i, t in enumerate(out["tokens"])]
    np.testing.assert_allclose(out["logprobs"], want, atol=TOL)


@pytest.mark.parametrize("bits", [2, 3, 4])
def test_kth_largest_is_the_sorts(bits):
    """The radix select at the digit widths PERF.md compares on the chip (32
    is a multiple of two of them): rows
    with -inf past their end, a run of zeros of both signs across the k-th
    place, and k from the first to past the valid keys."""
    rng = np.random.default_rng(bits)
    x = rng.normal(size=(12, 300)).astype(np.float32)
    x[:, 250:] = -np.inf
    x[3, :200] = 0.0
    x[5, 10:40] = -0.0
    x[7, :] = np.float32(1.5)
    for k in (1, 7, 64, 250, 300):
        keys, kth = kth_largest(jnp.asarray(x), k, bits)
        keys, kth = np.asarray(keys), np.asarray(kth)
        want = np.sort(x, -1)[:, ::-1][:, k - 1]
        got = np.take_along_axis(x, np.argmax(keys == kth, -1)[:, None], -1)
        np.testing.assert_array_equal(got[:, 0], want)    # (-0.0 == 0.0)
        # the keys' order is the floats'
        for row in (0, 5):
            order = np.argsort(keys[row], kind="stable")
            np.testing.assert_array_equal(x[row][order], np.sort(x[row]))


@pytest.mark.parametrize("start,t", [(0, 24), (5, 16), (8, 8), (13, 40),
                                     (63, 3), (150, 42)])
def test_chunk_write_equals_token_writes_in_three_pools(start, t):
    """A chunk's rows go into the three pools as its tokens would one by one
    (the indexer's keys page by page, two tokens to a row of its pool),
    whatever the offset of the first in its page, and nothing else moves."""
    cfg = _cfg()
    rng = np.random.default_rng(start)
    cache = _row_cache(cfg)
    cache = cache.with_pools([jnp.asarray(rng.normal(size=p.shape), p.dtype)
                              for p in cache.pools()])
    assert cache.idx_pages.shape[-2:] == (PS // 8, 8 * cfg.index_dim)
    k, v = (jnp.asarray(rng.normal(size=(1, t, cfg.n_kv_heads, cfg.head_dim)),
                        jnp.float32) for _ in range(2))
    ki = jnp.asarray(rng.normal(size=(1, t, cfg.index_dim)), jnp.float32)
    positions = start + jnp.arange(t)[None]
    chunked = jax.jit(write_layer_tokens, static_argnums=(1,))(
        cache, 1, k, v, positions, ki)
    by_token = cache
    for i in range(t):
        by_token = write_layer_tokens(by_token, 1, k[:, i:i + 1],
                                      v[:, i:i + 1], positions[:, i:i + 1],
                                      ki[:, i:i + 1])
    for got, want, was in zip(chunked.pools(), by_token.pools(),
                              cache.pools()):
        np.testing.assert_array_equal(got, want)
        assert not np.array_equal(got, was)


def test_control_without_the_selection_fails_above_topk(model):
    """The tolerance sees the mechanism: with every causal key attended the
    comparison holds up to index_topk tokens and fails past them."""
    cfg, m, params = model
    toks = _tokens(70, 70)
    got, _ = m.apply(params, jnp.asarray(toks)[None])
    dense = _reference_logits(params, toks, cfg, select=False)
    err = np.abs(np.asarray(got[0] - dense)).max(-1)
    assert err[:cfg.index_topk].max() < TOL
    assert err[cfg.index_topk + 8:].max() > 50 * TOL


def test_grouped_product_equals_one_hot_dropless_on_mixtral_tiny():
    cfg = LlamaConfig.moe_tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    assert not grouped_product(cfg.n_experts, cfg.moe_top_k)
    assert grouped_product(128, 8) and grouped_product(16, 2)
    import dataclasses
    cfg = dataclasses.replace(
        cfg, capacity_factor=cfg.n_experts / cfg.moe_top_k)   # dropless
    layer = MoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 11, cfg.d_model))
    params = layer.init(jax.random.PRNGKey(1), x)
    one_hot = layer.apply(params, x)
    p = params["params"]
    xf = x.reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(xf @ p["router"]["kernel"], -1)
    vals, idx = jax.lax.top_k(probs, cfg.moe_top_k)
    vals = vals / vals.sum(-1, keepdims=True)
    grouped = grouped_experts(xf, vals, idx, p["w_gate"], p["w_up"],
                              p["w_down"]).reshape(x.shape)
    np.testing.assert_allclose(grouped, one_hot, atol=1e-5)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

def _llm_cfg(**kw):
    base = dict(preset="keye_tiny", max_batch_slots=2, max_seq_len=192,
                paged=True, page_size=PS, prefill_chunk=32, decode_chunk=4,
                dtype="float32", param_dtype="float32", seed=5)
    base.update(kw)
    return LLMConfig(**base)


@pytest.fixture(scope="module")
def loop():
    return asyncio.new_event_loop()


def _generate(loop, srv, prompt, n, **kw):
    return loop.run_until_complete(asyncio.wait_for(
        srv.generate(prompt, max_tokens=n, **kw), 300))


def test_engine_builds_three_pools_from_the_schema(loop):
    srv = LLMServer(_llm_cfg(num_pages=40))
    try:
        c, mc = srv.cache, srv.model_cfg
        assert c.page_axis == 1 and len(c.pools()) == 3
        assert c.k_pages.shape == (mc.n_layers, 40, PS, mc.n_kv_heads,
                                   mc.head_dim)
        assert c.idx_pages.size == mc.n_layers * 40 * PS * mc.index_dim
        assert c.index_dim == mc.index_dim and c.page_size == PS
        dense = LLMServer(LLMConfig(preset="moe_tiny", paged=True, page_size=PS,
                                    max_seq_len=64, max_batch_slots=2))
        assert dense.cache.idx_pages is None and dense.cache.page_axis == 2
        assert len(jax.tree_util.tree_leaves(dense.cache)) == 4
        dense.close()
        with pytest.raises(ValueError, match="paged=True"):
            LLMServer(_llm_cfg(paged=False))
    finally:
        srv.close()


@pytest.mark.parametrize("n_prompt", [10, 50, 100])
def test_engine_logprobs_agree_with_reference_and_chunk_equals_steps(
        model, loop, n_prompt):
    """Through `generate`: chunked prefill (a first chunk of 32 > topk, then
    continuation chunks), then the fused decode chunk; the same with single
    steps must give the same tokens and log-probabilities bit for bit."""
    cfg, _, params = model
    prompt = _tokens(n_prompt, n_prompt)
    outs = []
    for chunk in (4, 1):
        srv = LLMServer(_llm_cfg(decode_chunk=chunk, num_pages=60),
                        params=params)
        try:
            outs.append(_generate(loop, srv, prompt, 11, logprobs=True))
        finally:
            srv.close()
    fused, single = outs
    assert fused["tokens"] == single["tokens"]
    np.testing.assert_array_equal(fused["logprobs"], single["logprobs"])
    seq = prompt + fused["tokens"]
    logp = jax.nn.log_softmax(_reference_logits(params, seq[:-1], cfg), -1)
    want = [float(logp[n_prompt - 1 + i, t])
            for i, t in enumerate(fused["tokens"])]
    np.testing.assert_allclose(fused["logprobs"], want, atol=TOL)


def _pool_bytes(srv, pid):
    axis = srv.cache.page_axis
    return [np.asarray(jnp.take(p, pid, axis=axis)).tobytes()
            for p in srv.cache.pools()]


def test_demote_and_restore_are_bit_exact_in_three_pools(model, loop):
    """Five prompts through a pool that holds two of them: the first one's
    pages are evicted, demoted to the stash (all three arrays a page) and
    restored when it is asked again; the restored pages are the bytes that
    left, and the answer is the same."""
    _, _, params = model
    srv = LLMServer(_llm_cfg(num_pages=15, max_seq_len=64, prefill_chunk=16),
                    params=params)
    try:
        prompts = [_tokens(n, 100 + n) for n in (28, 25, 26, 27, 33)]
        first = _generate(loop, srv, prompts[0], 9)
        before = {tuple(node.tokens) if hasattr(node, "tokens") else i:
                  _pool_bytes(srv, node.page)
                  for i, node in enumerate(srv.page_mgr._walk(prompts[0]))}
        assert len(before) == 3          # the prompt's three full pages
        for p in prompts[1:]:
            _generate(loop, srv, p, 9)
        d = srv.stats()["decode"]
        assert d["demoted_pages"] >= 3 and d["demote_failed"] == 0
        handle_bytes = sum(
            int(np.prod(p.shape)) // p.shape[1] * p.dtype.itemsize
            for p in srv.cache.pools())
        assert d["demote_bytes"] == d["demoted_pages"] * handle_bytes
        again = _generate(loop, srv, prompts[0], 9)
        d = srv.stats()["decode"]
        assert d["restored_pages"] >= 3
        assert again["tokens"] == first["tokens"]
        after = [_pool_bytes(srv, node.page)
                 for node in srv.page_mgr._walk(prompts[0])]
        assert after == list(before.values())
    finally:
        srv.close()


def test_stash_handle_records_every_array(tmp_path):
    from ray_tpu.serve.kv_transfer import KVPageStash
    stash = KVPageStash(budget_bytes=1 << 20)
    try:
        rng = np.random.default_rng(0)
        k = rng.normal(size=(3, 2, 8, 2, 16)).astype(np.float32)
        idx = rng.integers(0, 255, (3, 2, 1, 64)).astype(np.uint8)
        layout = [{"shape": list(k.shape[1:]), "dtype": "float32"}] * 2 + [
            {"shape": [2, 1, 64], "dtype": "uint8"}]
        handles = [stash.new_handle(layout) for _ in range(2)]
        assert handles[0]["blocks"] == layout
        assert handles[0]["nbytes"] == 2 * k[0].nbytes + idx[0].nbytes
        assert stash.put(handles, k, k + 1, idx).result(60) == [None, None]
        for i, h in enumerate(handles):
            gk, gv, gi = stash.get(h)
            assert (gk.tobytes(), gv.tobytes(), gi.tobytes()) == (
                k[i].tobytes(), (k + 1)[i].tobytes(), idx[i].tobytes())
            assert gi.dtype == np.uint8 and gi.shape == (2, 1, 64)
        # a hand-off that lacks an array the handle records is an error
        assert isinstance(stash.put(handles[:1], k, k).result(60)[0],
                          ValueError)
    finally:
        stash.close()


def test_pd_hand_off_is_bit_exact_in_three_pools(model, loop):
    from ray_tpu.ops.paged_attention import gather_pages
    from ray_tpu.serve.kv_transfer import ShipReader
    from ray_tpu.serve.pd import PDServer, PrefillServer
    _, _, params = model
    kw = dict(num_pages=60, prefix_cache=False)
    plain = LLMServer(_llm_cfg(**kw), params=params)
    prefill = PrefillServer(_llm_cfg(**kw), params=params)
    pd = PDServer(_llm_cfg(**kw), params=params, prefill=prefill)
    prompt = _tokens(77, 77)
    want = _generate(loop, plain, prompt, 9, logprobs=True)
    got = _generate(loop, pd, prompt, 9, logprobs=True)
    assert got["tokens"] == want["tokens"]
    np.testing.assert_allclose(got["logprobs"], want["logprobs"], atol=1e-6)
    assert pd.stats()["pd_requests"] == 1

    # the pages the decode replica holds are the prefill's, bit for bit
    def held(srv, rows):
        return [np.asarray(b).tobytes() for b in gather_pages(
            srv.cache, jnp.asarray(rows, jnp.int32), page_major=False)]

    async def both():
        free = set(prefill._free)
        header = await prefill.prefill_begin(prompt)
        (p_slot,) = free - set(prefill._free)
        n = header["total_pages"]
        p_rows = prefill.page_mgr.table_slice(p_slot, 0, n)
        slot, _ = await pd._reserve(prompt, len(prompt) + 1, use_prefix=False)
        reader, have, res = ShipReader(), 0, {"done": False}
        while not res["done"]:
            res = await prefill.prefill_wait(header["ship_id"], have)
            have += len(res["segments"])
            for seg in res["segments"]:
                att = await reader.fetch(
                    seg, header["layout"], header["data_addr"],
                    rpc_fetch=lambda oid: prefill.prefill_fetch(
                        header["ship_id"], oid))
                assert len(att.blocks) == 3
                pd._install_pages(slot, seg["page_start"], seg["n_pages"],
                                  att.blocks, len(prompt))
                att.close()
        await prefill.prefill_drop(header["ship_id"])
        # (the prefill replica has let go of its pages by now; nothing has
        # written them since)
        return held(prefill, p_rows), held(pd, pd.page_mgr.table_slice(
            slot, 0, n)), slot
    sent, back, slot = loop.run_until_complete(both())
    assert len(sent) == len(back) == 3 and sent == back
    pd._release_slot(slot)


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------

# (a row's first context, its steps, the engine's max_seq_len: the scoring
# kernel's walk covers a context rounded up to its block, 32 pages of 8 or
# the table's width where that is less: the last two cross a block's edge)
@pytest.mark.parametrize("first,steps,max_seq_len", [
    (3, 5, 192), (14, 6, 192), (16, 1, 192), (17, 9, 192), (40, 8, 192),
    (10, 0, 192), (250, 8, 512), (257, 4, 512)])
def test_sparse_counters_arithmetic(loop, first, steps, max_seq_len):
    srv = LLMServer(_llm_cfg(num_pages=20, max_seq_len=max_seq_len))
    try:
        topk = srv.model_cfg.index_topk
        srv._count_sparse(first, steps)
        contexts = [first + j for j in range(steps)]
        got = srv.stats()["sparse"]
        assert got["decode_rows"] == steps
        assert got["context_keys"] == sum(contexts)
        block = min(32 * PS, max_seq_len)
        assert index_block_keys(PS, max_seq_len // PS) == block
        assert got["scored_keys"] == sum(-(-c // block) * block
                                         for c in contexts)
        assert got["selected_keys"] == sum(min(c, topk) for c in contexts)
        assert got["dense_rows"] == sum(c <= topk for c in contexts)
        assert got["topk"] == topk
        assert got["index_pool_bytes"] == srv.cache.idx_pages.nbytes == (
            2 * 20 * PS * srv.model_cfg.index_dim * 4)
    finally:
        srv.close()


def test_moe_and_sparse_counters_follow_a_request(model, loop):
    _, _, params = model
    srv = LLMServer(_llm_cfg(num_pages=40), params=params)
    try:
        n_prompt, n_out = 50, 9
        _generate(loop, srv, _tokens(n_prompt, 1), n_out)
        st = srv.stats()
        mc, B = srv.model_cfg, srv.config.max_batch_slots
        k_layers = mc.moe_top_k * mc.n_layers
        # prefill: 50 tokens in chunks of 32 and 18 (padded to 32); decode: the
        # first token comes from the prefill, the other 8 from decode steps
        steps = st["decode"]["decode_steps"]
        assert st["moe"]["routed_rows"] == (n_prompt + n_out - 1) * k_layers
        assert st["moe"]["computed_rows"] == (32 + 32 + steps * B) * k_layers
        # counted on the device, handed back with the chunk's tokens: every
        # step runs every layer, and a layer's B x top_k rows reach between
        # top_k and B x top_k experts
        calls = steps * mc.n_layers
        assert st["moe"]["decode_layer_calls"] == calls
        assert (mc.moe_top_k * calls <= st["moe"]["decode_experts_touched"]
                <= B * mc.moe_top_k * calls)
        # and sync by sync with its time, for a reader that times a slice
        recent = st["moe"]["recent_decode_syncs"]
        assert len(recent) == st["decode"]["host_syncs"]
        assert [sum(r[i] for r in recent) for i in (1, 2)] == [
            calls, st["moe"]["decode_experts_touched"]]
        assert recent == sorted(recent) and recent[-1][0] <= time.monotonic()
        sp = st["sparse"]
        contexts = [n_prompt + 1 + j for j in range(n_out - 1)]
        assert sp["decode_rows"] == n_out - 1
        assert sp["context_keys"] == sum(contexts)
        assert sp["selected_keys"] == (n_out - 1) * mc.index_topk
        assert sp["dense_rows"] == 0
        # Mixtral's tiny preset on the one-hot dispatch: E x C rows a call
        dense = LLMServer(LLMConfig(preset="moe_tiny", paged=True, page_size=PS,
                                    max_seq_len=64, max_batch_slots=2,
                                    prefill_chunk=16))
        _generate(loop, dense, _tokens(10, 2), 3)
        moe = dense.stats()["moe"]
        dc = dense.model_cfg
        assert "sparse" not in dense.stats()
        assert moe["routed_rows"] == (10 + 2) * dc.moe_top_k * dc.n_layers
        calls = [16] + [2] * dense.stats()["decode"]["decode_steps"]
        assert moe["computed_rows"] == sum(
            dc.n_experts * s for s in calls) * dc.n_layers   # C = S: dropless
        assert moe["decode_layer_calls"] == moe["decode_experts_touched"] == 0
        dense.close()
    finally:
        srv.close()
