"""The dense paged cache stays where it is (PR 31): the decode kernel reads
the stacked pool `[L, Kh, P, page, D]` by (layer, page), a prefill chunk's
rows are written page by page in place, and no equation of a decode step or
of a prefill chunk produces an array the size of a layer's pool or of the
whole pool other than the in-place writes."""

import asyncio
import math
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.paged_attention import (PagedKVCache, paged_attention,
                                         paged_attention_reference,
                                         row_keys_values, write_layer_tokens,
                                         write_tokens)
from test_paged_attention import _random_paged


# -- (a) the kernel on the stacked pool --------------------------------------

def _stacked(layers, g, seed=0):
    """A stacked pool with scrambled tables, the pools as numpy (a test
    poisons them): rows of length 1, rows that end mid-page (13, 9) and one
    that fills its table (32)."""
    q, k, v, tbl, lens = _random_paged(4, 2, g, 64, 8, 4,
                                       np.array([1, 13, 32, 9]), seed=seed,
                                       layers=layers)
    return q, np.array(k), np.array(v), tbl, lens


@pytest.mark.parametrize("g", [1, 4])
@pytest.mark.parametrize("layers,layer", [(1, 0), (4, 0), (4, 1), (4, 2),
                                          (4, 3)])
def test_kernel_reads_its_layer_of_the_stacked_pool(layers, layer, g):
    q, k, v, tbl, lens = _stacked(layers, g)
    got = paged_attention(q, jnp.array(k), jnp.array(v), layer, tbl, lens,
                          interpret=True)
    want = paged_attention_reference(q, jnp.array(k), jnp.array(v), layer,
                                     tbl, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # the reference against one layer's pool handed in alone
    alone = paged_attention_reference(q, jnp.array(k[layer:layer + 1]),
                                      jnp.array(v[layer:layer + 1]), 0, tbl,
                                      lens)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(alone))


@pytest.mark.parametrize("layer", [0, 1, 2, 3])
def test_other_layers_garbage_does_not_reach_the_kernel(layer):
    q, k, v, tbl, lens = _stacked(4, 4, seed=3)
    clean = paged_attention(q, jnp.array(k), jnp.array(v), layer, tbl, lens,
                            interpret=True)
    for pool in (k, v):
        keep = pool[layer].copy()
        pool[:] = np.nan
        pool[layer] = keep
    # the layer as a traced value: one program for every layer
    dirty = jax.jit(lambda *a: paged_attention(*a, interpret=True))(
        q, jnp.array(k), jnp.array(v), jnp.int32(layer), tbl, lens)
    assert np.isfinite(np.asarray(dirty)).all()
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


def test_row_pages_kernel_equals_the_gather():
    """The continuation's page copy (`row_keys_values`), kernel against XLA's
    gather: the same rows, bit for bit, from a fragmented table."""
    _, k, v, tbl, lens = _stacked(3, 1, seed=5)
    cache = PagedKVCache(k_pages=jnp.array(k), v_pages=jnp.array(v),
                         block_tables=tbl, lengths=lens)
    for layer in range(3):
        want = row_keys_values(cache, layer)                  # XLA on the CPU
        got = row_keys_values(cache, layer, interpret=True)
        for w, g_ in zip(want, got):
            assert w.shape == (4, 32, 2, 64)
            np.testing.assert_array_equal(np.asarray(w), np.asarray(g_))
        # slot s of row 2 is absolute position s
        np.testing.assert_array_equal(
            np.asarray(want[0])[2, 19], k[layer, :, int(tbl[2, 2]), 3])


# -- (b) a prefill chunk's rows, written in place ----------------------------

PAGE = 64


def _filled_cache(rng, layers=2, kh=2, d=8, rows=2, max_pages=5):
    """A cache whose pools hold noise everywhere (an untouched slot that
    changes shows) and whose rows own scrambled pages."""
    pool = rows * max_pages + 1
    shape = (layers, kh, pool, PAGE, d)
    perm = rng.permutation(np.arange(1, pool)).reshape(rows, max_pages)
    return PagedKVCache(
        k_pages=jnp.array(rng.normal(size=shape).astype(np.float32)),
        v_pages=jnp.array(rng.normal(size=shape).astype(np.float32)),
        block_tables=jnp.array(perm.astype(np.int32)),
        lengths=jnp.zeros((rows,), jnp.int32))


@pytest.mark.parametrize("start", [0, 1, 63, 64, 65])
@pytest.mark.parametrize("length", [64, 63, 65, 2, 70])
def test_chunk_write_equals_row_writes_and_scatter(start, length):
    """Chunk starts on, just after and just before a page edge; chunks that
    end on an edge (0 + 64, 1 + 63, 63 + 65, 64 + 64, 65 + 63) and off it."""
    rng = np.random.default_rng(start * 131 + length)
    cache = _filled_cache(rng)
    layers, kh, _, _, d = cache.k_pages.shape
    rows = cache.block_tables.shape[0]
    k_new, v_new = (jnp.array(rng.normal(
        size=(layers, rows, length, kh, d)).astype(np.float32))
        for _ in range(2))
    # the rows start a page apart, so they end on different sides of an edge
    first = np.array([start, start + PAGE])
    positions = jnp.array(first[:, None] + np.arange(length)[None])

    chunked = cache
    for l in range(layers):
        chunked = jax.jit(write_layer_tokens, static_argnums=(1,))(
            chunked, l, k_new[l], v_new[l], positions)
    by_row = cache
    for l in range(layers):
        for t in range(length):
            by_row = write_layer_tokens(by_row, l, k_new[l][:, t:t + 1],
                                        v_new[l][:, t:t + 1],
                                        positions[:, t:t + 1])
    scattered = write_tokens(cache, k_new, v_new, positions)
    for got in (chunked.k_pages, chunked.v_pages):
        assert got.shape == cache.k_pages.shape
    for a, b, c in ((chunked.k_pages, by_row.k_pages, scattered.k_pages),
                    (chunked.v_pages, by_row.v_pages, scattered.v_pages)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    # and it wrote: the first token of row 0, layer 1
    page = int(cache.block_tables[0, start // PAGE])
    np.testing.assert_array_equal(
        np.asarray(chunked.k_pages)[1, :, page, start % PAGE],
        np.asarray(k_new)[1, 0, 0])


def test_chunk_write_drops_what_lies_past_the_table():
    """A padded chunk that runs past the row's last page leaves every page
    as it was there (the scatter's clamp wrote such rows into the last
    page)."""
    rng = np.random.default_rng(11)
    cache = _filled_cache(rng, rows=1, max_pages=2)
    new = jnp.array(rng.normal(size=(1, 96, 2, 8)).astype(np.float32))
    positions = jnp.array(64 + np.arange(96)[None])    # 64..159 of 128 slots
    got = write_layer_tokens(cache, 0, new, new, positions)
    want = write_layer_tokens(cache, 0, new[:, :64], new[:, :64],
                              positions[:, :64])
    np.testing.assert_array_equal(np.asarray(got.k_pages),
                                  np.asarray(want.k_pages))


def _greedy(model, params, cache, prompt, chunks, steps):
    """Prompt in `chunks` (lists of token counts), then `steps` greedy decode
    steps of one token; returns (tokens, cache)."""
    at, logits = 0, None
    for n in chunks:
        logits, cache = model.apply(params, prompt[:, at:at + n], cache=cache,
                                    paged_chunk_local=(at == 0 and n > 1))
        at += n
    toks = [int(jnp.argmax(logits[0, -1]))]
    for _ in range(steps - 1):
        logits, cache = model.apply(
            params, jnp.array([[toks[-1]]], jnp.int32), cache=cache)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks, cache


@pytest.mark.parametrize("chunks", [(24, 16), (32, 16), (17, 31), (1, 47)])
def test_two_chunk_prefill_and_decode_equal_the_row_path(chunks):
    """A prompt in two chunks (the second starting mid-page, on an edge, one
    past it, or at position 1) and twelve decode steps give the tokens and the
    pools that feeding every token through the T == 1 path gives."""
    from ray_tpu.models.llama import Llama, LlamaConfig
    from ray_tpu.serve.radix_cache import PageManager

    cfg = LlamaConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32,
                           max_seq_len=64)
    model = Llama(cfg)
    total = sum(chunks)
    prompt = jnp.array(np.random.default_rng(2).integers(
        1, cfg.vocab_size, size=(1, total)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), prompt[:, :8])

    def fresh():
        mgr = PageManager(num_pages=12, page_size=16, batch_slots=1,
                          max_pages_per_seq=4)
        row = mgr.allocate(0, total + 12)
        cache = PagedKVCache.init(
            cfg.n_layers, cfg.n_kv_heads, cfg.head_dim, num_pages=12,
            page_size=16, batch_slots=1, max_pages_per_seq=4,
            dtype=jnp.float32)
        return cache.replace(block_tables=jnp.array([row], jnp.int32))

    toks_c, cache_c = _greedy(model, params, fresh(), prompt, chunks, 12)
    toks_r, cache_r = _greedy(model, params, fresh(), prompt, (1,) * total, 12)
    assert toks_c == toks_r
    assert int(cache_c.lengths[0]) == int(cache_r.lengths[0]) == total + 11
    # the attention of a chunk and of a row differ in the order they sum in
    np.testing.assert_allclose(np.asarray(cache_c.k_pages),
                               np.asarray(cache_r.k_pages), atol=2e-5)
    np.testing.assert_allclose(np.asarray(cache_c.v_pages),
                               np.asarray(cache_r.v_pages), atol=2e-5)


@pytest.mark.parametrize("prefill_chunk,prompt_len", [(24, 40), (32, 64)])
def test_server_two_chunks_then_three_decode_chunks(prefill_chunk,
                                                    prompt_len):
    """A seeded `LLMServer` on the paged cache: a prompt of two prefill
    chunks (the second from position 24, mid-page, or 32, on an edge) and 13
    tokens (the first, then three fused chunks of 4) equal the dense slot
    cache's, token for token."""
    from ray_tpu.serve.llm import LLMConfig, LLMServer

    common = dict(preset="tiny", max_batch_slots=2, max_seq_len=128,
                  temperature=0.0, seed=7, param_dtype="float32",
                  dtype="float32", prefill_chunk=prefill_chunk,
                  decode_chunk=4)
    dense = LLMServer(LLMConfig(**common))
    paged = LLMServer(LLMConfig(**common, paged=True, page_size=16),
                      params=dense.params)
    prompt = [int(t) for t in np.random.default_rng(5).integers(
        1, 250, size=prompt_len)]
    want = asyncio.run(dense.generate(prompt, max_tokens=13))
    got = asyncio.run(paged.generate(prompt, max_tokens=13))
    assert got["tokens"] == want["tokens"] and len(got["tokens"]) == 13
    st = paged.stats()
    assert st["decode"]["prefill_chunks"] == 2
    assert st["active"] == 0


# -- (c) nothing the size of a pool is produced but by the in-place writes ---

def _equations(jaxpr):
    """Every equation of `jaxpr` that holds no jaxpr of its own, nested ones
    included (a call's outputs are its body's)."""
    for eqn in jaxpr.eqns:
        inner = []
        for value in eqn.params.values():
            for item in (value if isinstance(value, (list, tuple))
                         else [value]):
                item = getattr(item, "jaxpr", item)
                if hasattr(item, "eqns"):
                    inner.append(item)
        if inner and eqn.primitive.name != "pallas_call":
            for sub in inner:
                yield from _equations(sub)
        else:
            yield eqn


def _pool_sized_producers(fn, *args, pool_shape):
    """Names of the primitives, other than dynamic_update_slice, with an
    output of a layer's pool's or the whole pool's element count, in `fn`
    traced as the TPU would run it (the kernels' branch)."""
    counts = {int(np.prod(pool_shape)), int(np.prod(pool_shape[1:]))}
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    eqns = list(_equations(jaxpr))
    found = sorted({
        eqn.primitive.name for eqn in eqns
        if eqn.primitive.name != "dynamic_update_slice"
        and any(int(np.prod(v.aval.shape)) in counts for v in eqn.outvars)})
    return found, [eqn.primitive.name for eqn in eqns]


def _tiny_model_and_cache(rows):
    from ray_tpu.models.llama import Llama, LlamaConfig

    cfg = LlamaConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32,
                           max_seq_len=96)
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    # 23 pages of 16: no other array of the program has 23 as a factor
    cache = PagedKVCache.init(cfg.n_layers, cfg.n_kv_heads, cfg.head_dim,
                              num_pages=23, page_size=16, batch_slots=rows,
                              max_pages_per_seq=6, dtype=jnp.float32)
    return cfg, model, params, cache


def test_a_decode_step_produces_nothing_pool_sized_but_its_writes():
    cfg, model, params, cache = _tiny_model_and_cache(rows=3)
    cache = cache.replace(lengths=jnp.array([5, 17, 1], jnp.int32))
    last = jnp.array([[3], [4], [5]], jnp.int32)
    found, names = _pool_sized_producers(
        lambda p, c, t: model.apply(p, t, cache=c), params, cache, last,
        pool_shape=cache.k_pages.shape)
    assert found == []
    assert names.count("pallas_call") == cfg.n_layers        # the kernel ran
    assert names.count("dynamic_update_slice") == 2 * 3 * cfg.n_layers


@pytest.mark.parametrize("chunk_local", [True, False])
def test_a_prefill_chunk_produces_nothing_pool_sized_but_its_writes(
        chunk_local):
    cfg, model, params, cache = _tiny_model_and_cache(rows=1)
    cache = cache.replace(lengths=jnp.array([0 if chunk_local else 24]))
    tokens = jnp.ones((1, 32), jnp.int32)
    found, names = _pool_sized_producers(
        lambda p, c, t: model.apply(p, t, cache=c,
                                    paged_chunk_local=chunk_local),
        params, cache, tokens, pool_shape=cache.k_pages.shape)
    assert found == []
    # 32 tokens from anywhere touch at most 3 pages of 16, k and v, a layer
    assert names.count("dynamic_update_slice") == 2 * 3 * cfg.n_layers


def test_the_guard_sees_a_layer_taken_out_of_the_pool():
    """What the guard is for: the form this PR removed is caught."""
    _, _, _, cache = _tiny_model_and_cache(rows=1)
    found, _ = _pool_sized_producers(
        lambda c: (c.k_pages[1], c.v_pages.at[0, :, 1, 2].set(0.0)), cache,
        pool_shape=cache.k_pages.shape)
    assert "scatter" in found and len(found) >= 2


# -- (c') the same, in the programs the v5e's compiler makes of them ----------
# The jaxpr cannot show a copy that XLA puts in to re-tile the pool for a
# gather or a scatter (what the parent's prefill did, four times a chunk), so
# the three programs are compiled for the chip, which needs no chip
# (on-chip-measurement guide, section 2). One process may hold the TPU's
# library: the topology is described in a fixture, in this file only.

@pytest.fixture(scope="module")
def v5e():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _pool_sized_results(hlo: str, pool: str) -> set:
    """(opcode, name) of every instruction of the compiled program whose
    result has the type `pool` (a regular expression: a layer's pool or the
    whole of it) and that neither passes it on nor writes into it in place."""
    import re

    passive = ("parameter", "get-tuple-element", "tuple", "bitcast")
    moved = set()
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ([\w\-]+)\(", line)
        if m and re.search(pool, m.group(2)) and m.group(3) not in passive:
            if "dynamic-update-slice" not in m.group(3) + m.group(1):
                moved.add((m.group(3), m.group(1)))
    return moved


def _step_compiled_for(v5e, cfg, pages, rows, width, tokens=1,
                       chunk_local=False, window_pages=0) -> str:
    """The text of the program the v5e's compiler makes of one step of a
    `Llama(cfg)` over a donated paged cache of `pages` pages of 64 (`rows`
    rows, `width` table entries a row): `tokens` new tokens a row. With
    `window_pages` the cache has a window pool of so many pages for the
    model's sliding layers beside the full pool."""
    from ray_tpu.models.llama import Llama

    model = Llama(cfg)

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=v5e), tree)

    params = on_chip(jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0)))
    window = ({"window": dict(layers=cfg.n_window_layers,
                              num_pages=window_pages)} if window_pages else {})
    if cfg.index_topk:          # the token-major layout and the third pool
        window = {"index_dim": cfg.index_dim}
    cache = on_chip(jax.eval_shape(lambda: PagedKVCache.init(
        cfg.n_layers - cfg.n_window_layers, cfg.n_kv_heads, cfg.head_dim,
        pages, 64, rows, width, **window)))

    def step(params, cache, toks):
        logits, cache = model.apply(params, toks, cache=cache,
                                    paged_chunk_local=chunk_local)
        return cache, logits[:, -1]

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        return jax.jit(step, donate_argnums=(1,)).lower(
            params, cache, jax.ShapeDtypeStruct((rows, tokens), jnp.int32,
                                                sharding=v5e)
        ).compile().as_text()


@pytest.mark.parametrize("name,rows,tokens,chunk_local", [
    ("decode", 4, 1, False), ("first_chunk", 1, 128, True),
    ("continuation", 1, 128, False)])
def test_compiled_for_the_v5e_no_program_moves_a_pool(v5e, name, rows,
                                                      tokens, chunk_local):
    from ray_tpu.models.llama import LlamaConfig

    # a small model over a pool as deep as the cells' (1152 pages of 64 x 128):
    # a pool of a few MB is moved between memory spaces whole, which is no fault
    cfg = LlamaConfig(vocab_size=256, d_model=256, n_layers=2, n_heads=4,
                      n_kv_heads=2, head_dim=128, ffn_dim=512,
                      max_seq_len=512, dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16)
    hlo = _step_compiled_for(v5e, cfg, 1152, rows, 8, tokens, chunk_local)
    moved = _pool_sized_results(hlo, r"bf16\[(2,)?2,1152,64,128\]")
    assert not moved, f"{name}: pool-sized results of {sorted(moved)}"
    assert "paged_decode" in hlo or tokens > 1
    assert ("paged_row_pages" in hlo) == (name == "continuation")


@pytest.mark.parametrize("cell,tokens,width,heads", [
    ("solar250b-agentloop-batch", 1024, 640, 64),
    ("mixtral8x7b-batch", 512, 36, 32)])
def test_continuation_compiled_for_the_v5e_at_the_cells_shapes(
        v5e, cell, tokens, width, heads):
    """A continuation chunk at a cell's chunk length, row capacity and heads
    (8 kv heads of 128, pages of 64 in bf16): the v5e's compiler takes
    `flash_continuation` with its prefetched start and a grid as long as the
    keys the chunk reaches, once a layer beside the page copy that feeds it;
    no score matrix is left in XLA's hands (no f32 array of the program is
    larger than the chunk's queries [T, H, D], which the rotary turns in f32:
    the scores of ONE key block of 512 were four times that), the
    row's K and V go from the copy to the kernel head-major as the pool
    holds them (nothing else has a row-sized result: no transpose), and
    nothing pool-sized is produced but the writes."""
    import re

    from ray_tpu.models.llama import LlamaConfig

    layers, pages, kv_heads, d = 2, 1152, 8, 128
    cfg = LlamaConfig(vocab_size=256, d_model=256, n_layers=layers,
                      n_heads=heads, n_kv_heads=kv_heads, head_dim=d,
                      ffn_dim=512, max_seq_len=width * 64, dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16)
    hlo = _step_compiled_for(v5e, cfg, pages, 1, width, tokens)
    calls = sorted(line.split(" = ")[0].strip().lstrip("%").split(".")[0]
                   for line in hlo.splitlines() if "tpu_custom_call" in line)
    assert calls == (["flash_continuation"] * layers
                     + ["paged_row_pages"] * layers), f"{cell}: {calls}"
    largest = max(math.prod(map(int, dims.split(",")))
                  for dims in re.findall(r"f32\[([\d,]+)\]", hlo))
    assert largest <= tokens * heads * d, f"{cell}: an f32 of {largest}"
    row = rf"bf16\[1,({kv_heads},{width},64|{kv_heads},{width * 64}|{width * 64},{kv_heads}),{d}\]"
    moved = {m for m in _pool_sized_results(hlo, row) if m[0] != "custom-call"}
    assert not moved, f"{cell}: row-sized results of {sorted(moved)}"
    moved = _pool_sized_results(
        hlo, rf"bf16\[({layers},)?{kv_heads},{pages},64,{d}\]")
    assert not moved, f"{cell}: pool-sized results of {sorted(moved)}"


@pytest.mark.parametrize("tokens", [512, 64, 16, 500])
def test_selected_prefill_compiled_for_the_v5e_at_the_cells_shapes(v5e,
                                                                   tokens):
    """`keye30b-longdoc-batch`'s continuation chunk (32 query heads on 4 kv
    heads of 128, a 16-head indexer of 64 that selects 2048, a row of 464
    pages of 64 in token-major pools of 12288), at the engine's buckets and
    at one clamped to what a row has left: the v5e's compiler produces
    nothing the size of a pool, the indexer's included, but the in-place
    writes; the second pass reads the row a key block at a time."""
    import re

    from ray_tpu.models.llama import LlamaConfig

    layers, pages, width = 2, 12288, 464
    cfg = LlamaConfig(vocab_size=256, d_model=256, n_layers=layers,
                      n_heads=32, n_kv_heads=4, head_dim=128, ffn_dim=512,
                      max_seq_len=width * 64, dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16, qk_norm=True, index_heads=16,
                      index_dim=64, index_topk=2048)
    hlo = _step_compiled_for(v5e, cfg, pages, 1, width, tokens)
    assert "tpu_custom_call" not in hlo
    assert not re.search(rf"bf16\[1,4,{width},64,128\]", hlo)   # no row copy
    pool = rf"bf16\[({layers},)?{pages},(64,4,128|32,128)\]"
    moved = {m for m in _pool_sized_results(hlo, pool) if m[0] != "scatter"}
    written = {name for line in hlo.splitlines() if re.search(
        r'op_name="[^"]*/scatter"', line)
        for name in re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = ", line)}
    assert not {m for m in moved if m[1] not in written}, sorted(moved)


def test_selected_decode_compiled_for_the_v5e_at_the_cells_shape(v5e):
    """`keye30b-longdoc-batch`'s decode step (24 rows of 464 pages of 64, a
    16-head indexer of 64 that selects 2048, token-major pools of 12288):
    the v5e's compiler takes the kernel `sparse_decode_scores` with the
    indexer's pool left in HBM and its own copies of a block's pages (the
    interpreter checks neither a copy's tiling nor the VMEM a block takes);
    it is in the program once a layer under the name a reader can look for;
    the step holds no sort, no copy of the indexer's keys (gathered
    `[rows x width, 32, 128]` or unpacked `[rows, width x 64, 64]`), and
    nothing the size of a pool but the in-place writes."""
    import re

    from ray_tpu.models.llama import LlamaConfig

    layers, pages, rows, width = 2, 12288, 24, 464
    cfg = LlamaConfig(vocab_size=256, d_model=256, n_layers=layers,
                      n_heads=32, n_kv_heads=4, head_dim=128, ffn_dim=512,
                      max_seq_len=width * 64, dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16, qk_norm=True, index_heads=16,
                      index_dim=64, index_topk=2048)
    hlo = _step_compiled_for(v5e, cfg, pages, rows, width)
    calls = [line.split(" = ")[0].strip().lstrip("%").split(".")[0]
             for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert calls == ["sparse_decode_scores"] * layers, calls
    assert not re.search(r" sort\(", hlo)
    assert not re.search(rf"bf16\[{rows * width},32,128\]", hlo)
    assert not re.search(rf"bf16\[{rows},{width * 64},64\]", hlo)
    pool = rf"bf16\[({layers},)?{pages},(64,4,128|32,128)\]"
    assert not _pool_sized_results(hlo, pool)


@pytest.mark.parametrize("cell,rows,width,heads,head_dim", [
    ("mixtral8x7b-batch", 32, 36, 32, 128),
    ("solar250b-agentloop-batch", 16, 640, 64, 128),
    ("mistral7b-chat", 32, 128, 32, 128),
    ("llama_1b-heads-of-64", 8, 16, 32, 64)])
def test_decode_compiled_for_the_v5e_at_the_cells_shapes(
        v5e, cell, rows, width, heads, head_dim):
    """The decode step at a cell's rows, table width and heads (8 kv heads,
    pages of 64 in bf16): the v5e's compiler takes `paged_decode` with a
    pool operand for every page of a block and a grid as long as the walk
    (the interpreter checks neither a block's tiling nor the VMEM the
    operands take), it is in the program once a layer under the name the
    benchmark's readers look for, and nothing pool-sized is produced but the
    writes. (A pool of heads of 64 XLA lays out pages-minor at the program's
    edge and copies whole, in and out: not held.)"""
    from ray_tpu.models.llama import LlamaConfig

    layers, pages = 2, 1152
    cfg = LlamaConfig(vocab_size=256, d_model=256, n_layers=layers,
                      n_heads=heads, n_kv_heads=8, head_dim=head_dim,
                      ffn_dim=512, max_seq_len=width * 64, dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16)
    hlo = _step_compiled_for(v5e, cfg, pages, rows, width)
    calls = [line.split(" = ")[0].strip().lstrip("%").split(".")[0]
             for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert calls == ["paged_decode"] * layers, f"{cell}: custom calls {calls}"
    moved = _pool_sized_results(
        hlo, rf"bf16\[({layers},)?8,{pages},64,{head_dim}\]")
    assert not moved or head_dim % 128, (
        f"{cell}: pool-sized results of {sorted(moved)}")


@pytest.mark.parametrize("name,rows,tokens,calls", [
    ("decode", 48, 1, ["paged_decode"] + ["paged_decode_window"] * 3),
    ("continuation", 1, 1024,
     ["flash_continuation"] + ["flash_continuation_window"] * 3
     + ["paged_row_pages"] + ["paged_row_pages_window"] * 3)])
def test_two_pools_compiled_for_the_v5e_at_the_cells_shapes(v5e, name, rows,
                                                            tokens, calls):
    """`commandaplus-mixedlen-batch`'s decode step and continuation chunk (48
    rows, a table of 800 pages, 128 query heads on 8 kv heads of 128, a window
    of 4096, one period of three sliding layers to one full): the v5e's
    compiler takes the window forms of the three kernels (a first block a
    row in the walk, a key grid that starts at the window, a copy of the
    window's pages only) under names of their own, once a sliding layer,
    beside the full layer's, and neither pool is moved."""
    from ray_tpu.models.llama import LlamaConfig

    pages, window_pages, d = 1152, 640, 128
    cfg = LlamaConfig(vocab_size=256, d_model=256, n_layers=4, n_heads=128,
                      n_kv_heads=8, head_dim=d, ffn_dim=512,
                      max_seq_len=800 * 64, dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16, rope_theta=50000.0,
                      layer_types=("sliding", "sliding", "sliding", "full"),
                      sliding_window=4096, rope_interleaved=True,
                      parallel_block=True, norm="layer")
    hlo = _step_compiled_for(v5e, cfg, pages, rows, 800, tokens,
                             window_pages=window_pages)
    got = sorted(line.split(" = ")[0].strip().lstrip("%").split(".")[0]
                 for line in hlo.splitlines() if "tpu_custom_call" in line)
    assert got == sorted(calls), f"{name}: custom calls {got}"
    for layers, n in ((1, pages), (3, window_pages)):
        moved = _pool_sized_results(hlo, rf"bf16\[({layers},)?8,{n},64,{d}\]")
        assert not moved, f"{name}: pool-sized results of {sorted(moved)}"


@pytest.mark.parametrize("name,rows,tokens", [("decode", 48, 1),
                                              ("continuation", 1, 1024)])
def test_split_rotary_compiled_for_the_v5e_moves_no_weight(v5e, name, rows,
                                                           tokens):
    """Command A+'s attention widths in the form `LLMServer` runs (PR 40:
    `split_rotary_pairs`): the rotary's pairs split at load, so the
    rotate-half form, and `wq`, `wk` of the three sliding layers stored
    [out, in]. The v5e's compiler then makes no `[128, 64, 2, 4096]` of `wq`
    (the interleaved form's pair reshape, folded through the projection:
    three relayouts a call) and transposes no rebuilt kernel; what is left
    with `wq`'s element count is ONE copy, of the full layer's `wq`, which is
    the caller's array, [in, out] as every model's is."""
    import re

    from ray_tpu.models.llama import LlamaConfig

    heads, d, d_model = 128, 128, 4096
    cfg = LlamaConfig(vocab_size=256, d_model=d_model, n_layers=4,
                      n_heads=heads, n_kv_heads=8, head_dim=d, ffn_dim=512,
                      max_seq_len=800 * 64, dtype=jnp.bfloat16,
                      param_dtype=jnp.bfloat16, rope_theta=50000.0,
                      layer_types=("sliding", "sliding", "sliding", "full"),
                      sliding_window=4096, rope_interleaved=False,
                      qk_out_major=True, parallel_block=True, norm="layer")
    hlo = _step_compiled_for(v5e, cfg, 1152, rows, 800, tokens,
                             window_pages=640)
    assert not re.search(rf"bf16\[{heads},{d // 2},2,{d_model}\]", hlo)
    sized = [(m.group(3), m.group(1)) for m in (
        re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = bf16\[([\d,]+)\]\S* ([\w\-]+)\(",
                 line) for line in hlo.splitlines())
        if m and math.prod(map(int, m.group(2).split(","))) == heads * d * d_model
        and m.group(3) in ("copy", "transpose", "reshape")]
    assert [op for op, _ in sized] == ["copy"], f"{name}: {sized}"
    line = next(ln for ln in hlo.splitlines() if f"%{sized[0][1]} = " in ln)
    assert "layers_3" in line, line


@pytest.mark.parametrize("cell,rows,k,n,held,tm", [
    ("commandaplus-mixedlen-batch chunk", 8192 + 16 * 128, 4096, 4096, 16, 128),
    ("commandaplus-mixedlen-batch decode", 384, 4096, 4096, 16, None),
    ("commandaplus-mixedlen-batch chunk of 512", 4096 + 16 * 128, 4096, 4096,
     16, 128),
    ("solar250b-agentloop-batch chunk gate/up", 8192, 4096, 1280, 40, None),
    ("solar250b-agentloop-batch chunk down", 8192, 1280, 4096, 40, None),
    ("keye30b-longdoc-batch chunk gate/up", 4096, 2048, 768, 128, None)])
def test_grouped_product_compiled_for_the_v5e_at_the_cells_shapes(
        v5e, cell, rows, k, n, held, tm):
    """megablox's `gmm` under the tiles a cell's grouped expert product takes
    (`_gmm_tiling`; `tm`: the row tile of a chunk whose groups
    `grouped_experts` lays on tile edges, the buffer a tile a group longer:
    PR 43): the v5e's compiler takes it (a tile of the matrix, the rows'
    tile, the result's and the f32 accumulator fit its fast memory, which the
    interpreter does not check) and the kernel is in the program under the
    name the benchmark's readers look for, with the rows in its result's
    shape."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from ray_tpu.models.moe import _GMM_EDGE_ROWS, _gmm_tiling

    tiling = _gmm_tiling(rows, k, n, 2)
    if tm is not None:
        assert tm == _GMM_EDGE_ROWS
        tiling = (tm,) + tiling[1:]
    shapes = [jax.ShapeDtypeStruct(s, t, sharding=v5e) for s, t in (
        ((rows, k), jnp.bfloat16), ((held, k, n), jnp.bfloat16),
        ((held,), jnp.int32))]
    hlo = jax.jit(lambda lhs, rhs, sizes: gmm(
        lhs, rhs, sizes, preferred_element_type=jnp.bfloat16,
        tiling=tiling)).lower(*shapes).compile().as_text()
    calls = [line.split(" = ")[0].split("%")[-1].split(".")[0]
             + " " + line.split(" = ")[1].split("{")[0]
             for line in hlo.splitlines() if "tpu_custom_call" in line]
    assert calls == [f"gmm bf16[{rows},{n}]"], f"{cell}: {calls}"
