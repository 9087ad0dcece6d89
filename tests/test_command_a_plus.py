"""Command A+'s layout at test sizes (window of 16 keys, pages of 4, two
periods of three sliding layers to one full) through `LLMServer`: prefill in
chunks whose edges do not fall on the window's, then decode through both
tables, cold and resumed, against the benchmark's plain reference
(`perfbench/references/command_a_plus.py`: float32, a full pass under explicit
masks, nothing of the program); the share of the experts tied to the uncut
layer; the norm and the rotary against the reference's; what such a model
refuses."""

import asyncio
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.builders import command_a_plus as builder  # noqa: E402
from perfbench.references import command_a_plus as reference  # noqa: E402
from ray_tpu.models.llama import LayerNorm, LlamaConfig  # noqa: E402
from ray_tpu.models.moe import MoEMLP  # noqa: E402
from ray_tpu.ops.attention import apply_rope  # noqa: E402
from ray_tpu.serve.llm import LLMConfig, LLMServer  # noqa: E402
from ray_tpu.serve.radix_cache import PageManager  # noqa: E402

SIZES = dict(builder.model_sizes({}, rehearse=True), experts_held=16)
WINDOW, PAGE = SIZES["window"], 4


def _server(**kw):
    cfg = dict(preset="command_tiny", model_overrides=builder._overrides(SIZES),
               param_dtype="float32", dtype="float32", paged=True,
               prefix_cache=True, max_batch_slots=4, page_size=PAGE,
               max_seq_len=192, prefill_chunk=12, decode_chunk=4,
               num_pages=200, num_window_pages=80)
    return LLMServer(LLMConfig(**{**cfg, **kw}))


@pytest.fixture(scope="module")
def server():
    srv = _server()
    yield srv
    srv.close()


def _errs(srv, prompt, out, weights_as=None):
    want = reference.logprobs_of(srv.params, prompt + out["tokens"], SIZES,
                                 len(out["tokens"]), weights_as=weights_as)
    return np.abs(np.asarray(out["logprobs"]) - np.asarray(want))


# under the window all the way; crossing it while decoding; several windows
# long before the first token (chunks of 12 end at 12, 24, 36, ..: not on 16s)
@pytest.mark.parametrize("n_prompt,n_out", [(5, 6), (11, 12), (75, 10)],
                         ids=["under", "crosses-in-decode", "windows-long"])
def test_chunked_prefill_then_decode_is_the_reference(server, n_prompt, n_out):
    rng = np.random.default_rng(n_prompt)
    prompt = rng.integers(0, 256, n_prompt).tolist()
    before = server.stats()["window"]
    out = asyncio.run(server.generate(prompt, max_tokens=n_out, logprobs=True))
    assert _errs(server, prompt, out).max() < 1e-4
    after = server.stats()["window"]
    released = after["window_pages_released"] - before["window_pages_released"]
    # a page goes back once the row's next query is a window and a page on
    total = n_prompt + n_out
    assert (released > 0) == (total > WINDOW + PAGE)
    assert after["window_pages_live"] == after["full_pages_live"] == 0
    # a lower precision does not pass for the same thing
    assert np.median(_errs(server, prompt, out, "float8_e4m3fn")) > 1e-3


def test_a_resumed_prompt_is_the_cold_one(server):
    """A prompt that extends a finished one resumes from its last page
    boundary on pages of BOTH pools (the full pool's of all of it, the window
    pool's of its last window), and reads what a cold server reads."""
    rng = np.random.default_rng(1)
    base = rng.integers(0, 256, 62).tolist()
    longer = base + rng.integers(0, 256, 21).tolist()

    async def run(srv):
        await srv.generate(base, max_tokens=3)
        before = srv.stats()
        out = await srv.generate(longer, max_tokens=8, logprobs=True)
        return before, out, srv.stats()

    before, warm, after = asyncio.run(run(server))
    assert after["prefix_hit_tokens"] - before["prefix_hit_tokens"] == 60
    for key in ("prefix_cut_by_window", "prefix_lost_to_window"):
        assert after["window"][key] == before["window"][key]
    cold_srv = _server(prefix_cache=False)
    cold = asyncio.run(cold_srv.generate(longer, max_tokens=8, logprobs=True))
    assert warm["tokens"] == cold["tokens"]
    np.testing.assert_allclose(warm["logprobs"], cold["logprobs"], atol=1e-5)
    assert _errs(server, longer, warm).max() < 1e-4


def test_concurrent_rows_of_both_kinds_keep_their_own_pages(server):
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, n).tolist() for n in (7, 90, 33, 50, 9, 64)]

    async def run():
        return await asyncio.gather(*(
            server.generate(p, max_tokens=9, logprobs=True) for p in prompts))

    for prompt, out in zip(prompts, asyncio.run(run())):
        assert _errs(server, prompt, out).max() < 1e-4


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """What the 8 chips that share a layer compute, the shared experts
    counted once, is the whole layer's experts as the reference has them."""
    cfg = LlamaConfig.command_tiny(dtype=jnp.float32,
                                   **builder._overrides(SIZES))
    whole = MoEMLP(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, cfg.d_model))
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    want = reference.experts_of(x[0], {"moe": params}, SIZES)

    def share(first, held=2):
        part = dict(params, **{k: params[k][first:first + held] if first < 16
                               else params[k][:held]
                               for k in ("w_gate", "w_up", "w_down")})
        mod = MoEMLP(LlamaConfig.command_tiny(
            dtype=jnp.float32, **{**builder._overrides(SIZES),
                                  "experts_held": held,
                                  "experts_first": first}))
        return mod.apply({"params": part}, x)[0]

    shared = share(1000)        # a bank none of whose experts is ever chosen
    routed = sum(share(first) - shared for first in range(0, 16, 2))
    np.testing.assert_allclose(routed + shared, want, atol=2e-5)
    # and the chip's own share is the reference's given the same share
    mine = reference.experts_of(
        x[0], {"moe": dict(params, **{k: params[k][:2] for k in
                                      ("w_gate", "w_up", "w_down")})},
        dict(SIZES, experts_first=0))
    np.testing.assert_allclose(share(0), mine, atol=2e-5)


def test_norm_and_rotary_are_the_references():
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 4, 16)) * 3 + 1
    norm = LayerNorm(1e-5, jnp.float32)
    scale = jax.random.normal(jax.random.PRNGKey(4), (16,))
    got = norm.apply({"params": {"scale": scale}}, x)
    np.testing.assert_allclose(got, reference.layer_norm(x, scale, 1e-5),
                               atol=1e-6)
    assert abs(float(got.mean())) < 1.0     # centred, then scaled
    pos = jnp.arange(9) * 5 + 3
    turned = apply_rope(x[:1], pos[None], 50000.0, interleaved=True)[0]
    np.testing.assert_allclose(
        turned, reference.rotary_interleaved(x[0], pos, 50000.0), atol=1e-5)


def test_what_a_model_with_two_kinds_of_page_refuses():
    with pytest.raises(ValueError, match="sliding-window layers.*paged=True"):
        LLMServer(LLMConfig(preset="command_tiny", paged=False))
    with pytest.raises(ValueError, match="no demotion hooks"):
        PageManager(32, 4, 2, 8, window_pages=16, window=16, window_budget=8,
                    demote_cb=lambda pid, node: None)
    from ray_tpu.serve import pd

    srv = _server(prefix_cache=False)
    with pytest.raises(NotImplementedError, match="window pool"):
        pd._require_paged(srv, "PrefillServer")
    # no demotion hooks are wired: an evicted page of either pool is discarded
    assert srv.page_mgr.demote_cb is None and srv.page_mgr.restore_cb is None
