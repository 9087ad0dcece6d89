"""A served model whose rotary is interleaved runs on weights whose rotary
pairs were split ONCE, when `LLMServer` took them, with the rotate-half form
(`models/llama.py split_rotary_pairs`): the engine's programs against
`model.apply` on the GIVEN weights with the interleaved form, what the engine
keeps of what it was given, the counter, and the traced chunk program."""

import asyncio
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import KVCache, Llama, LlamaConfig, split_rotary_pairs
from ray_tpu.serve.llm import LLMConfig, LLMServer
from test_paged_pool_in_place import _equations

WINDOW, CHUNK = 16, 12          # `command_tiny`'s window; the engine's chunk
ENGINE = dict(param_dtype="float32", dtype="float32", paged=True,
              prefix_cache=True, max_batch_slots=4, page_size=4,
              max_seq_len=96, prefill_chunk=CHUNK, decode_chunk=4,
              num_pages=120)


@pytest.fixture(scope="module")
def servers():
    made = {}

    def get(preset):
        if preset not in made:
            extra = ({"num_window_pages": 60} if preset == "command_tiny"
                     else {})
            cfg = LLMConfig(preset=preset, **ENGINE, **extra)
            model_cfg = getattr(LlamaConfig, preset)(
                max_seq_len=96, dtype=jnp.float32, param_dtype=jnp.float32)
            given = Llama(model_cfg).init(jax.random.PRNGKey(3),
                                          jnp.zeros((1, 8), jnp.int32))
            made[preset] = (LLMServer(cfg, params=given), given)
        return made[preset]

    yield get
    for srv, _ in made.values():
        srv.close()


def _leaf(tree, layer, name):
    return tree["params"][f"layers_{layer}"]["attn"][name]["kernel"]


# the first token comes from the first chunk's logits (a prompt inside one
# chunk), from a continuation chunk whose queries have the window's edge
# behind them (chunks end at 12, 24, 30; the window is 16), and the decode
# steps run on past the window
@pytest.mark.parametrize("n_prompt,n_out", [(9, 1), (30, 1), (20, 14)],
                         ids=["first-chunk", "continuation-past-the-window",
                              "decode-past-the-window"])
def test_engine_on_split_weights_is_the_given_model(servers, n_prompt, n_out):
    srv, given = servers("command_tiny")
    assert n_prompt + n_out > WINDOW or n_prompt < CHUNK
    prompt = np.random.default_rng(n_prompt).integers(0, 256, n_prompt).tolist()
    out = asyncio.run(srv.generate(prompt, max_tokens=n_out, logprobs=True))
    sequence = prompt + out["tokens"]
    # the uncached forward of the given pair: the interleaved form
    assert srv.model_cfg.rope_interleaved
    logits = srv.model.apply(given, jnp.asarray(sequence)[None])[0][0]
    logp = np.asarray(jax.nn.log_softmax(logits.astype(jnp.float32), -1))
    want = [logp[n_prompt - 1 + i, t] for i, t in enumerate(out["tokens"])]
    np.testing.assert_allclose(out["logprobs"], want, atol=1e-4)


@pytest.mark.parametrize("preset,split", [("command_tiny", 12), ("moe_tiny", 0),
                                          ("tiny", 0)])
def test_what_the_engine_keeps_and_counts(servers, preset, split):
    srv, given = servers(preset)
    same = lambda a, b: all(x is y for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))
    # the public pair is the caller's, and agrees with itself
    assert same(srv.params, given)
    assert srv.model.cfg is srv.model_cfg
    assert srv.stats()["decode"]["rotary_split_projections"] == split
    if not split:
        assert srv._run_params is srv.params and srv._run_model is srv.model
        return
    cfg = srv.model_cfg
    ran_cfg = srv._run_model.cfg
    assert cfg.rope_interleaved and not cfg.qk_out_major
    assert ran_cfg.qk_out_major and not ran_cfg.rope_interleaved
    sliding = [i for i in range(cfg.n_layers) if cfg.layer_kind(i) != "full"]
    assert split == 2 * len(sliding)
    for layer in range(cfg.n_layers):
        for name in ("wq", "wk", "wv", "wo"):
            ran, was = (_leaf(t, layer, name) for t in (srv._run_params, given))
            if layer in sliding and name in ("wq", "wk"):
                # a head's columns [0, 2, 4, ..., 1, 3, 5, ...], stored
                # [out, in]
                heads = np.asarray(was).reshape(was.shape[0], -1, cfg.head_dim)
                want = np.concatenate([heads[..., 0::2], heads[..., 1::2]], -1)
                np.testing.assert_array_equal(
                    np.asarray(ran), want.reshape(was.shape).T)
            else:       # a full (NoPE) layer's, the values', the output's
                assert ran is was, (layer, name)


@pytest.mark.parametrize("extra", [{}, {"qk_norm": True},
                                   {"rope_sections": (2, 3, 3)}],
                         ids=["plain", "qk-norm", "sections"])
def test_split_pair_through_a_cache_is_the_interleaved_pair(extra):
    """The pure function on a model with no layer pattern: a prefill and
    decode steps through the dense cache, the given pair against the split
    one, with a q/k norm whose scale is not all ones and with rotary
    sections (a frequency keeps its index in both forms)."""
    cfg = LlamaConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32,
                           rope_interleaved=True, **extra)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    params = Llama(cfg).init(jax.random.PRNGKey(0), toks)
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: (x + jnp.arange(x.size, dtype=x.dtype) / x.size
                         if path[-1].key == "scale" and x.ndim == 1 else x),
        params)
    split, half = split_rotary_pairs(params, cfg)
    assert half.qk_out_major and not half.rope_interleaved
    n_split = sum(a is not b for a, b in zip(
        jax.tree_util.tree_leaves(split), jax.tree_util.tree_leaves(params)))
    assert n_split == (4 if extra.get("qk_norm") else 2) * cfg.n_layers

    def run(model_cfg, tree):
        model, cache, outs = Llama(model_cfg), KVCache.init(model_cfg, 2, 32), []
        for piece in (toks[:, :8], toks[:, 8:9], toks[:, 9:10]):
            logits, cache = model.apply(tree, piece, cache=cache)
            outs.append(np.asarray(logits))
        return np.concatenate(outs, 1)

    np.testing.assert_allclose(run(half, split), run(cfg, params), atol=2e-5)
    # and a model whose rotary is not interleaved gets its arguments back
    plain = LlamaConfig.tiny()
    assert split_rotary_pairs(params, plain) == (params, plain)


def _traced(fn, *args):
    """The equations of `fn`, traced as the TPU would run it."""
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        return list(_equations(jax.make_jaxpr(fn)(*args).jaxpr))


def _pair_reshapes(eqns, head_dim):
    """Reshapes of a head's last dimension to (D/2, 2): the interleaved
    form, which the compiler folds through the projection into `wq`."""
    return [e for e in eqns if e.primitive.name == "reshape"
            and tuple(e.outvars[0].aval.shape[-2:]) == (head_dim // 2, 2)]


@pytest.mark.parametrize("start", [0, 32], ids=["first", "continuation"])
def test_the_chunk_program_holds_no_pair_reshape(servers, start):
    """The engine's prefill chunk program (16 tokens from `start`) holds no
    pair reshape, and produces nothing with `wq`'s element count at all (the
    product's result is [tokens, heads x D], the weight itself an argument,
    read [out, in] as it is stored)."""
    srv, given = servers("command_tiny")
    tokens = np.zeros((1, 16), np.int32)
    eqns = _traced(
        lambda p, c: srv._prefill.__wrapped__(
            p, c, tokens, 0, jnp.int32(start), jnp.int32(start + 16),
            start == 0), srv._run_params, srv.cache)
    assert sum(e.primitive.name == "dot_general" for e in eqns) > 8
    assert _pair_reshapes(eqns, srv.model_cfg.head_dim) == []
    wq = _leaf(given, 0, "wq")
    assert sorted({e.primitive.name for e in eqns for v in e.outvars
                   if int(np.prod(v.aval.shape)) == wq.size}) == []

    # what the check is for: the given pair's program has the reshape
    def interleaved(params, cache):
        row = cache.replace(block_tables=cache.block_tables[:1],
                            win_tables=cache.win_tables[:1],
                            lengths=jnp.zeros((1,), jnp.int32))
        return srv.model.apply(params, tokens, cache=row,
                               paged_chunk_local=True, mutable=["moe_stats"])

    assert _pair_reshapes(_traced(interleaved, given, srv.cache),
                          srv.model_cfg.head_dim)
