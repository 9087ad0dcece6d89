"""Falcon-H1's block at test sizes on the CPU: the chunked SSD program and the
one-token update against the token-by-token scan; `LLMServer` (prefill in
chunks, then decode through a cache with a state AND pages in every layer)
against the plain reference `perfbench/references/falcon_h1.py` on seeded
weights, cold and resumed from a branch snapshot; that the comparison is not
blind to either branch; 5 query heads a kv head through the two attention
kernels' paths."""

import asyncio
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import stats
from perfbench.builders import falcon_h1 as builder
from perfbench.references import falcon_h1 as reference
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.attention import decode_attention
from ray_tpu.ops.paged_attention import (paged_attention,
                                         paged_attention_reference)
from ray_tpu.ops.ssd import ssd_chunked, ssd_recurrent, ssd_step
from ray_tpu.serve.llm import LLMConfig, LLMServer

fa = importlib.import_module("ray_tpu.ops.flash_attention")

CONFIG = json.load(open(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "perfbench", "configs", "falcon-h1-34b-serve.json")))
SIZES = builder.model_sizes(CONFIG, rehearse=True)


def _operands(rows, t, h=8, p=16, g=2, n=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(ks[0], (rows, t, h, p)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (rows, t, h)) - 1.0),
        a=-jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5),
        b=jax.random.normal(ks[3], (rows, t, g, n)),
        c=jax.random.normal(ks[4], (rows, t, g, n)),
        d=jax.random.normal(ks[5], (h,)),
        state=jax.random.normal(ks[6], (rows, h, n, p)))


def _cut(ops, lo, hi, rows=slice(None)):
    return {k: (v[rows, lo:hi] if k in ("x", "dt", "b", "c") else v)
            for k, v in ops.items()}


@pytest.mark.parametrize("t,chunk", [(37, 8), (64, 16), (5, 8)])
def test_the_chunked_program_is_the_recurrence(t, chunk):
    ops = _operands(2, t)
    with jax.default_matmul_precision("highest"):
        want_y, want_s = ssd_recurrent(**ops)
        got_y, got_s = ssd_chunked(**ops, chunk=chunk)
    np.testing.assert_allclose(got_y, want_y, atol=2e-4, rtol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5, rtol=2e-5)


def test_a_bucket_s_padding_leaves_the_state_at_n_valid():
    ops = _operands(2, 40)
    n_valid = jnp.array([23, 40])
    with jax.default_matmul_precision("highest"):
        got_y, got_s = ssd_chunked(**ops, n_valid=n_valid, chunk=8)
        short_y, short_s = ssd_recurrent(**{
            **_cut(ops, 0, 23, slice(0, 1)), "state": ops["state"][:1]})
        whole_y, whole_s = ssd_recurrent(**ops)
    np.testing.assert_allclose(got_y[0, :23], short_y[0], atol=2e-4)
    np.testing.assert_allclose(got_s[0], short_s[0], atol=2e-5)
    np.testing.assert_allclose(got_s[1], whole_s[1], atol=2e-5)


def test_a_chunk_boundary_inside_a_prompt_carries_the_state():
    """Two calls, the second from the first one's state, cut where no chunk
    of the program ends: one call over the whole."""
    ops = _operands(1, 45)
    with jax.default_matmul_precision("highest"):
        want_y, want_s = ssd_recurrent(**ops)
        y1, s1 = ssd_chunked(**_cut(ops, 0, 19), chunk=8)
        y2, s2 = ssd_chunked(**{**_cut(ops, 19, 45), "state": s1}, chunk=8)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), want_y, atol=2e-4)
    np.testing.assert_allclose(s2, want_s, atol=2e-5)


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["xla", "kernel_interpreted"])
def test_the_one_token_update_is_a_step_of_the_recurrence(interpret):
    """Heads of 128 over a state of 128 in groups of 8 heads: what the kernel
    takes. An inactive slot's state is left alone, to the bit."""
    ops = _operands(3, 1, h=16, p=128, g=2, n=128, seed=3)
    step = {k: (v[:, 0] if k in ("x", "dt", "b", "c") else v)
            for k, v in ops.items()}
    valid = jnp.array([True, False, True])
    with jax.default_matmul_precision("highest"):
        want_y, want_s = ssd_recurrent(**ops)
        got_y, got_s = ssd_step(**step, valid=valid, interpret=interpret)
    live = np.array([0, 2])
    np.testing.assert_allclose(got_y[live], want_y[live, 0], atol=2e-4)
    np.testing.assert_allclose(got_s[live], want_s[live], atol=2e-5)
    np.testing.assert_array_equal(got_s[1], ops["state"][1])


def test_the_prefill_kernel_is_the_recurrence():
    """The pallas form of the chunked program, interpreted: chunks of 128,
    heads of 128 over a state of 128, four heads of a group a step; a carried
    state, a length that is no whole chunk and a bucket's padding."""
    ops = _operands(1, 300, h=8, p=128, g=2, n=128, seed=5)
    with jax.default_matmul_precision("highest"):
        want_y, want_s = ssd_recurrent(**_cut(ops, 0, 211))
        got_y, got_s = ssd_chunked(**ops, n_valid=jnp.array([211]),
                                   chunk=128, interpret=True)
        xla_y, xla_s = ssd_chunked(**ops, n_valid=jnp.array([211]), chunk=128)
    np.testing.assert_allclose(got_y[:, :211], want_y, atol=1e-3, rtol=1e-4)
    np.testing.assert_allclose(got_s, want_s, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(got_y, xla_y, atol=1e-3, rtol=1e-4)
    np.testing.assert_allclose(got_s, xla_s, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------- the engine
ENGINE = dict(preset="falcon_h1_tiny", paged=True, prefix_cache=True,
              page_size=8, prefill_chunk=16, max_seq_len=128, num_pages=120,
              max_batch_slots=4, decode_chunk=4, num_snapshots=6,
              dtype="float32", param_dtype="float32")


def _server(seed=0):
    cfg = LlamaConfig.falcon_h1_tiny(max_seq_len=128, dtype=jnp.float32,
                                     param_dtype=jnp.float32)
    params = builder.seeded_params(cfg, seed, builder.weight_scales(SIZES))
    return LLMServer(LLMConfig(**ENGINE), params=params)


@pytest.fixture(scope="module")
def server():
    srv = _server()
    yield srv
    srv.close()


def _generate(srv, prompt, n=6):
    out = asyncio.run(srv.generate(prompt, max_tokens=n, logprobs=True))
    return list(out["tokens"]), np.asarray(out["logprobs"], np.float64)


def _reference(srv, prompt, toks, **kw):
    return np.asarray(reference.logprobs_of(
        srv.params, list(prompt) + toks, SIZES, len(toks), **kw), np.float64)


def test_the_engine_is_the_reference_cold_and_from_a_branch_snapshot(server):
    """Four prompts behind one system prompt of 37 tokens (4 whole pages):
    the first is cold and saves at its own end, the second finds the pages
    and no state, prefills everything again and saves where it leaves the
    first, the third and fourth resume there. Every one agrees with the
    reference's full pass in f32 to 1e-4."""
    rng = np.random.default_rng(0)
    system = rng.integers(0, 256, 37).tolist()
    seen = []
    for i in range(4):
        prompt = system + rng.integers(0, 256, 5 + 7 * i).tolist()
        toks, got = _generate(server, prompt)
        np.testing.assert_allclose(got, _reference(server, prompt, toks),
                                   atol=1e-4)
        seen.append(dict(server.stats()["state"]))
    assert seen[0]["branch_snapshots_saved"] == 0
    assert seen[1]["branch_snapshots_saved"] == 1
    assert seen[1]["snapshot_hits"] == 0
    assert [s["branch_snapshot_hits"] for s in seen] == [0, 0, 1, 2]
    assert seen[3]["branch_snapshots_saved"] == 1       # one a node
    assert seen[3]["resume_gap_tokens"] == 0
    st = server.stats()
    assert st["prefix_hit_tokens"] == 2 * 32
    cache = server.cache
    assert len(cache.state) == 2 and cache.k_pages.shape[0] == 2
    per_slot = 2 * (8 * 32 * 16 * 4 + 3 * 256 * 4)
    assert st["state"]["slot_state_bytes"] == 4 * per_slot
    assert st["state"]["snapshot_pool_bytes"] == 6 * per_slot


def test_the_check_is_blind_to_neither_branch(server):
    """The reference with the mixer's branch left out, and with attention's,
    each lies past the configuration's tolerance from the engine: a check on
    these weights cannot pass whatever a branch does."""
    tol = CONFIG["check"]
    rng = np.random.default_rng(7)
    errs = {"both": [], "mixer": [], "attention": []}
    for n in (40, 70, 25):
        prompt = rng.integers(0, 256, n).tolist()
        toks, got = _generate(server, prompt, 9)
        for left, branches in (("both", ("mixer", "attention")),
                               ("mixer", ("attention",)),
                               ("attention", ("mixer",))):
            want = _reference(server, prompt, toks, branches=branches)
            errs[left] += np.abs(got - want).tolist()
    agree = lambda e: stats.logprobs_agree(
        {"abs_logprob_errs": e, "finite": True}, tol)
    assert agree(errs["both"])
    assert not agree(errs["mixer"]) and not agree(errs["attention"])


def test_the_hand_off_refuses_the_model():
    from ray_tpu.serve.pd import PrefillServer
    with pytest.raises(ValueError, match="paged=True"):
        LLMServer(LLMConfig(preset="falcon_h1_tiny", paged=False))
    srv = PrefillServer(LLMConfig(**ENGINE))
    try:   # the hand-off carries pages, not state: asked for, it says so
        with pytest.raises(NotImplementedError, match="recurrent state"):
            asyncio.run(srv.prefill_begin([1, 2, 3]))
    finally:
        srv.close()


def test_the_snapshot_pool_is_the_engines_setting():
    srv = LLMServer(LLMConfig(**{**ENGINE, "num_snapshots": None}))
    try:
        assert srv.page_mgr.snapshots == 4 * 4          # four a slot
        assert srv.cache.snap_state[0].shape[0] == 16
    finally:
        srv.close()


# ------------------------------------------------- 5 query heads a kv head
def test_paged_decode_at_five_heads_a_kv_head():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    rows, kh, g, d, pages, ps = 3, 2, 5, 128, 12, 8
    q = jax.random.normal(ks[0], (rows, kh * g, d))
    k = jax.random.normal(ks[1], (2, kh, pages, ps, d))
    v = jax.random.normal(ks[2], (2, kh, pages, ps, d))
    tables = jnp.array([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 9]], jnp.int32)
    lengths = jnp.array([19, 9, 32], jnp.int32)
    want = paged_attention_reference(q, k, v, 1, tables, lengths)
    got = paged_attention(q, k, v, 1, tables, lengths, interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # and the reference against plain attention over the row's own keys
    k_row = k[1, :, tables[0]].transpose(1, 0, 2, 3).reshape(kh, -1, d)
    s = jnp.einsum("kgd,ksd->kgs", q[0].reshape(kh, g, d), k_row[:, :19])
    v_row = v[1, :, tables[0]].transpose(1, 0, 2, 3).reshape(kh, -1, d)
    plain = jnp.einsum("kgs,ksd->kgd", jax.nn.softmax(s / d ** 0.5, -1),
                       v_row[:, :19])
    np.testing.assert_allclose(want[0].reshape(kh, g, d), plain, atol=2e-5)


@pytest.mark.parametrize("t,start", [(16, 21), (128, 64), (128, 37)])
def test_flash_continuation_at_five_heads_a_kv_head(t, start, monkeypatch):
    """204 queries would tile no chunk: the block is the power of two under
    it (32 x 5 rows at the test's 160 where the chip has 128 x 5 at 1024)."""
    monkeypatch.setattr(fa, "_CONT_ROWS", 160)
    monkeypatch.setattr(fa, "_CONT_BLOCK_KV", 32)
    assert fa.continuation_blocks(t, 5, jnp.float32) == min(t, 32)
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    kh, g, d, cap = 2, 5, 64, 256
    q = jax.random.normal(ks[0], (1, t, kh * g, d))
    k = jax.random.normal(ks[1], (1, cap, kh, d))
    v = jax.random.normal(ks[2], (1, cap, kh, d))
    starts = jnp.array([start], jnp.int32)
    want = decode_attention(q, k, v, starts)
    got = fa.flash_continuation(q, k.swapaxes(1, 2), v.swapaxes(1, 2), starts,
                                interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_blocks_of_the_other_group_sizes_are_what_they_were():
    for g, want in ((1, 1024), (4, 256), (8, 128), (16, 64)):
        assert fa.continuation_blocks(1024, g, jnp.bfloat16) == want
    assert fa.continuation_blocks(1024, 5, jnp.bfloat16) == 128
    assert fa.continuation_blocks(200, 5, jnp.bfloat16) is None
