"""`flash_continuation`, the kernel a prefill continuation chunk's attention
runs as on the TPU, in interpret mode on the CPU: against `decode_attention`
(all keys at once under the same absolute-position mask) over chunk lengths,
starts, group sizes, head sizes and both precisions, with the row's tail and
the table's placeholder pages poisoned (the key bound), and through
`LLMServer` at test sizes against the uncached forward. And the kernel's
trace at the configurations' shapes, held to what PR 38 measured."""

import asyncio
import hashlib
import importlib
from functools import partial
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops.attention import (blockwise_prefill_attention,
                                   decode_attention)
from ray_tpu.ops.paged_attention import PagedKVCache, row_pages
from ray_tpu.serve.llm import LLMConfig, LLMServer

# the package exports the function `flash_attention` over the module's name
fa = importlib.import_module("ray_tpu.ops.flash_attention")

KV_HEADS = 2
CAPACITY = 304          # 38 pages of 8: 9.5 key blocks of 32, the last partial
ON_THE_CHIP = (fa._CONT_ROWS, fa._CONT_BLOCK_KV)    # as imported: PR 38's


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """The kernel's blocks at a size where a chunk of 256 is many of them:
    64 stacked rows and 32 keys a step (1024 and 512 on the chip)."""
    monkeypatch.setattr(fa, "_CONT_ROWS", 64)
    monkeypatch.setattr(fa, "_CONT_BLOCK_KV", 32)


def _operands(t, g, d, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, t, KV_HEADS * g, d), dtype)
    k = jax.random.normal(ks[1], (1, CAPACITY, KV_HEADS, d), dtype)
    v = jax.random.normal(ks[2], (1, CAPACITY, KV_HEADS, d), dtype)
    return q, k, v


def _close(got, want, dtype):
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-5 if dtype == jnp.float32
                               else 2e-2)


AT_EDGE = 64                 # a key block's edge
INSIDE = 37                  # inside a key block
# start None: the chunk ends where the row's capacity does
CASES = [
    (16, INSIDE, 1, 64, jnp.float32), (16, AT_EDGE, 4, 128, jnp.bfloat16),
    (16, None, 8, 64, jnp.bfloat16), (16, 5, 8, 128, jnp.float32),
    (64, INSIDE, 1, 128, jnp.bfloat16), (64, INSIDE, 4, 64, jnp.float32),
    (64, INSIDE, 8, 128, jnp.float32), (64, AT_EDGE, 1, 64, jnp.float32),
    (64, AT_EDGE, 4, 128, jnp.float32), (64, AT_EDGE, 8, 64, jnp.bfloat16),
    (64, None, 1, 128, jnp.float32), (64, None, 4, 64, jnp.bfloat16),
    (64, None, 8, 128, jnp.bfloat16), (64, 0, 4, 64, jnp.float32),
    (256, INSIDE, 1, 64, jnp.bfloat16), (256, INSIDE, 4, 128, jnp.float32),
    (256, INSIDE, 8, 64, jnp.float32), (256, 32, 8, 128, jnp.bfloat16),
    (256, None, 1, 128, jnp.float32), (256, None, 4, 64, jnp.bfloat16),
    (256, None, 8, 128, jnp.float32),
]


@pytest.mark.parametrize(
    "t,start,g,d,dtype", CASES,
    ids=[f"T{t}-start{'_to_capacity' if s is None else s}-G{g}-D{d}-"
         f"{jnp.dtype(dt).name}" for t, s, g, d, dt in CASES])
def test_kernel_is_decode_attention(t, start, g, d, dtype):
    start = CAPACITY - t if start is None else start
    q, k, v = _operands(t, g, d, dtype)
    at = jnp.array([start], jnp.int32)
    got = jax.jit(partial(fa.flash_continuation, interpret=True))(
        q, k.swapaxes(1, 2), v.swapaxes(1, 2), at)
    _close(got, decode_attention(q, k, v, at), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("t,start", [(64, INSIDE), (16, 100), (256, 0)])
def test_nothing_past_the_chunks_last_key_is_read(t, start, dtype):
    """The row through its pages: the placeholder page that the table names
    past the row's end is NaN, and so is every slot of the row's last page
    past the chunk's last key. The output is finite and what clean keys give:
    no key block past start + T - 1 is folded, and in the last one that is
    the tail is read as nothing."""
    g, d, page, layers = 4, 64, 8, 2
    q, k, v = _operands(t, g, d, dtype, seed=1)
    at = jnp.array([start], jnp.int32)
    want = decode_attention(q, k, v, at)

    width = CAPACITY // page
    held = -(-(start + t) // page)                  # pages the row holds
    table = np.zeros((1, width), np.int32)          # placeholder: page 0
    table[0, :held] = 1 + np.random.default_rng(0).permutation(held)
    past = np.arange(CAPACITY) > start + t - 1
    pools = []
    for x in (k, v):
        x = jnp.where(past[None, :, None, None], jnp.nan, x)
        pages = x[0].reshape(width, page, KV_HEADS, d)[:held]   # by position
        pool = jnp.full((layers, KV_HEADS, 1 + held, page, d), jnp.nan, dtype)
        pools.append(pool.at[1, :, table[0, :held]].set(
            pages.transpose(0, 2, 1, 3)))
    cache = PagedKVCache(k_pages=pools[0], v_pages=pools[1],
                         block_tables=jnp.asarray(table),
                         lengths=jnp.array([start], jnp.int32))
    k_row, v_row = row_pages(cache, 1, interpret=True)
    assert bool(jnp.isnan(k_row).any()) and k_row.shape == (
        1, KV_HEADS, width, page, d)
    got = fa.flash_continuation(q, k_row, v_row, at, interpret=True)
    _close(got, want, dtype)


@pytest.mark.parametrize("t,start,key_block", [(24, 37, 32), (64, 64, 48),
                                               (40, CAPACITY - 40, 512)])
def test_the_xla_form_by_key_blocks_is_decode_attention(t, start, key_block):
    """What a chunk the kernel cannot take falls to where the scores of a
    whole row would not fit: any length, any key block."""
    q, k, v = _operands(t, 4, 64, jnp.float32, seed=2)
    at = jnp.array([start], jnp.int32)
    got = blockwise_prefill_attention(q, k, v, at, key_block=key_block)
    _close(got, decode_attention(q, k, v, at), jnp.float32)


@pytest.mark.parametrize("t,g,dtype,block_q", [
    (1024, 8, jnp.bfloat16, 16), (16, 8, jnp.bfloat16, 16),
    (256, 1, jnp.float32, 64), (24, 4, jnp.float32, None),
    (40, 4, jnp.float32, None), (48, 1, jnp.bfloat16, 48),
    (200, 4, jnp.bfloat16, None), (8, 4, jnp.bfloat16, None)])
def test_a_chunk_the_blocks_do_not_tile_is_not_the_kernels(t, g, dtype,
                                                          block_q):
    """Whole query blocks of whole sublane tiles (8 rows of f32, 16 of
    bf16), chosen by the chunk's shape; anything else says so."""
    assert fa.continuation_blocks(t, g, dtype) == block_q
    if block_q is None:
        q, k, v = _operands(t, g, 64, dtype)
        with pytest.raises(ValueError, match="no query block tiles"):
            fa.flash_continuation(q, k.swapaxes(1, 2), v.swapaxes(1, 2),
                                  jnp.array([0], jnp.int32), interpret=True)


# -- the other configurations' trace ---------------------------------------

# sha256 of `_traced_text` on PR 43's tree (commit d9cdbc9)
AS_MEASURED = {
    "solar250b-agentloop-batch": (
        (1024, 64, 8, 40960, None),
        "d2c457197b21ddafc678761b4dab7de553d3af17af37c723dc1ac2a303b40ea6"),
    "commandaplus-mixedlen-batch full": (
        (1024, 128, 8, 51200, None),
        "4611ee3d7d6a6ae4d4edbd887cde18c7e30b43b6e4dc9dedbb2642ec37557b19"),
    "commandaplus-mixedlen-batch window": (
        (1024, 128, 8, 51200, 4096),
        "bd4317ba8d2515890321648aa423bd74daa66ca5446a6d589b9cee0ae111a080"),
}


def _traced_text(t, heads, kv_heads, capacity, window) -> str:
    """What `flash_continuation` traces to at a cell's shapes (heads of 128,
    pages of 64, bf16) with the blocks the chip runs: the jaxpr, the kernel's
    body, grid, operands and name in it, and the program it lowers to in
    interpret mode. Neither holds a line number."""
    q = jax.ShapeDtypeStruct((1, t, heads, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, kv_heads, capacity // 64, 64, 128),
                              jnp.bfloat16)
    at = jax.ShapeDtypeStruct((1,), jnp.int32)
    kw = {} if window is None else {"window": window}
    return (str(jax.make_jaxpr(partial(fa.flash_continuation, **kw))(
        q, kv, kv, at)) + jax.jit(partial(
            fa.flash_continuation, interpret=True, **kw)).lower(
                q, kv, kv, at).as_text())


@pytest.mark.parametrize("cell", sorted(AS_MEASURED))
def test_the_kernel_traces_to_what_it_did(cell, monkeypatch):
    """Solar's and Command A+'s continuation kernel, block sizes included,
    is textually the one PR 38 measured: a change meant for another layer's
    mask (Keye's selection, say) shows here if it moves them."""
    monkeypatch.setattr(fa, "_CONT_ROWS", ON_THE_CHIP[0])
    monkeypatch.setattr(fa, "_CONT_BLOCK_KV", ON_THE_CHIP[1])
    shapes, was = AS_MEASURED[cell]
    text = _traced_text(*shapes)
    assert "name=flash_continuation" in text
    assert hashlib.sha256(text.encode()).hexdigest() == was


# -- through the engine ------------------------------------------------------

def _on_the_kernel(traced: list):
    """`llama._continuation_attention` as it runs on the TPU, its kernels in
    interpret mode: the dispatch itself is the program's."""
    dispatch = llama._continuation_attention

    def as_on_tpu(q, cache, layer_idx, positions):
        traced.append(q.shape[1])
        with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
                mock.patch.object(llama, "row_pages",
                                  partial(row_pages, interpret=True)), \
                mock.patch.object(llama, "flash_continuation", partial(
                    fa.flash_continuation, interpret=True)):
            return dispatch(q, cache, layer_idx, positions)
    return mock.patch.object(llama, "_continuation_attention", as_on_tpu)


@pytest.mark.parametrize("preset", ["tiny", "moe_tiny", "solar_tiny"])
def test_chunked_and_resumed_prefill_is_the_uncached_forward(preset):
    """A prompt prefilled in three chunks and more, then one that resumes
    from a prefix hit on it, the continuation chunks on the kernel: the
    logprobs of the uncached forward, and the counters of what it did."""
    traced = []
    with _on_the_kernel(traced):
        srv = LLMServer(LLMConfig(
            preset=preset, dtype="float32", param_dtype="float32",
            paged=True, prefix_cache=True, max_batch_slots=2, page_size=8,
            max_seq_len=128, prefill_chunk=32, decode_chunk=4, num_pages=64))
        assert [srv.stats()["decode"][k] for k in (
            "continuation_chunks", "continuation_reach_keys",
            "continuation_query_keys")] == [0, 0, 0]
        rng = np.random.default_rng(5)
        first = rng.integers(0, 256, 77).tolist()
        second = first + rng.integers(0, 256, 30).tolist()

        async def run():
            a = await srv.generate(first, max_tokens=4, logprobs=True)
            mid = srv.stats()
            b = await srv.generate(second, max_tokens=4, logprobs=True)
            return a, mid, b, srv.stats()

        try:
            a, mid, b, end = asyncio.run(run())
        finally:
            srv.close()
    assert set(traced) == {16, 32}                 # the kernel was traced

    for prompt, out in ((first, a), (second, b)):
        toks = prompt + out["tokens"]
        logits = srv.model.apply(srv.params, jnp.asarray(toks)[None])[0][0]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        want = [float(logp[i - 1, toks[i]])
                for i in range(len(prompt), len(toks))]
        np.testing.assert_allclose(out["logprobs"], want, atol=1e-4)

    # the first prompt: chunks at 0, 32, 64 (a model with state stops at its
    # last page boundary, 72, and runs the tail as a chunk of its own)
    stateful = preset == "solar_tiny"
    d = mid["decode"]
    assert d["continuation_chunks"] == (3 if stateful else 2)
    assert d["continuation_reach_keys"] == 64 + (72 + 77 if stateful else 77)
    # the second resumed from the first one's pages (or snapshot)
    hit = end["prefix_hit_tokens"] - mid["prefix_hit_tokens"]
    assert hit == 72
    chunks = end["decode"]["continuation_chunks"] - d["continuation_chunks"]
    reach = (end["decode"]["continuation_reach_keys"]
             - d["continuation_reach_keys"])
    # 35 new tokens from 72: a chunk of 32 (to 104, the last page boundary)
    # and the tail
    assert (chunks, reach) == (2, 104 + 107)
    # query j of a chunk at `start` sees start + j + 1 keys
    pairs = (end["decode"]["continuation_query_keys"]
             - d["continuation_query_keys"])
    assert pairs == sum(range(73, 108))
