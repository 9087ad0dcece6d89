"""Solar-Open2's layout at test sizes through `LLMServer`: linear-attention
layers with a state a slot beside one full-attention layer in four, snapshots
of the state in the prefix cache, a chip's share of the experts. The judge is
the benchmark's plain reference (`perfbench/references/solar_open2.py`: float32,
the token recurrence, nothing of the program)."""

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

from perfbench.builders import solar_open2 as builder  # noqa: E402
from perfbench.references import solar_open2 as reference  # noqa: E402
from ray_tpu.models.llama import Llama, LlamaConfig  # noqa: E402
from ray_tpu.serve.llm import LLMConfig, LLMServer  # noqa: E402

SIZES = builder.model_sizes({}, rehearse=True)
PAGE = 8


def _server(**kw):
    cfg = dict(preset="solar_tiny", model_overrides=builder._overrides(SIZES),
               param_dtype="float32", dtype="float32", paged=True,
               prefix_cache=True, max_batch_slots=4, page_size=PAGE,
               max_seq_len=256, prefill_chunk=32, decode_chunk=4,
               num_pages=160)
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


def test_cold_and_resumed_agree_with_the_reference(server):
    """Prefill in chunks (the state carried, the stop at the last page
    boundary, a padded tail) then decode through the cache, cold and RESUMED
    from a snapshot: two prompts that share a prefix past a page boundary."""
    rng = np.random.default_rng(0)
    base = rng.integers(0, 256, 77).tolist()
    longer = base + rng.integers(0, 256, 45).tolist()

    async def run():
        first = await server.generate(base, max_tokens=6, logprobs=True)
        before = server.stats()
        second = await server.generate(longer, max_tokens=6, logprobs=True)
        return first, second, before, server.stats()

    first, second, before, after = asyncio.run(run())
    assert _errs(server, base, first).max() < 1e-4
    assert _errs(server, longer, second).max() < 1e-4
    # the second prompt resumed from the first one's snapshot, taken where its
    # prefill crossed its last page boundary: 77 tokens -> 9 pages, 72 tokens
    assert (after["state"]["snapshot_hits"]
            - before["state"]["snapshot_hits"]) == 1
    assert after["prefix_hit_tokens"] - before["prefix_hit_tokens"] == 72
    assert after["state"]["restore_copies"] == before["state"]["restore_copies"] + 1
    assert after["state"]["snapshots_saved"] == before["state"]["snapshots_saved"] + 1
    # a lower precision does not pass for the same thing: the reference on
    # weights rounded to float8 is far from the engine
    assert np.median(_errs(server, longer, second, "float8_e4m3fn")) > 1e-2


def test_concurrent_rows_keep_their_own_state(server):
    """Decode steps of other slots run between a prompt's prefill chunks: a
    slot that is not decoding must keep its state, whatever the batch does."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).tolist() for n in (20, 90, 130, 64, 65, 33)]

    async def run():
        return await asyncio.gather(*[
            server.generate(p, max_tokens=9, logprobs=True) for p in prompts])

    for prompt, out in zip(prompts, asyncio.run(run())):
        assert _errs(server, prompt, out).max() < 1e-4, len(prompt)


def test_counters_of_the_share_and_the_state(server):
    prompt = np.random.default_rng(4).integers(0, 256, 60).tolist()
    asyncio.run(server.generate(prompt, max_tokens=4))
    st = server.stats()
    moe, state = st["moe"], st["state"]
    # 4 of 16 experts are held: about a quarter of the pairs fall on them
    assert 0.1 < moe["held_pairs"] / moe["routed_rows"] < 0.4
    assert moe["computed_rows"] >= moe["held_pairs"]
    assert state["slot_state_bytes"] == 4 * 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert state["snapshot_pool_bytes"] == 4 * state["slot_state_bytes"]
    assert set(st["decode"]["phase_s"]) >= {"state_save", "state_restore"}
    # pages above an evicted snapshot serve nobody: nothing is demoted
    assert server.page_mgr.demote_cb is None and st["decode"]["demoted_pages"] == 0
    assert st["decode"]["phase_n"]["state_save"] == state["snapshot_copies"]


def test_an_evicted_snapshot_is_a_miss_and_still_right():
    """A pool of two snapshots: the third prompt's save evicts the first
    one's, so asking the first again prefills from nothing, and agrees."""
    srv = _server(max_batch_slots=1)
    srv.page_mgr.snapshots = 2        # a smaller pool than 4 a slot
    srv.page_mgr._snap_free = [1, 0]
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, 40).tolist() for _ in range(3)]

    async def run():
        for p in prompts:
            await srv.generate(p, max_tokens=2)
        hits = srv.page_mgr.snapshot_hits
        out = await srv.generate(prompts[0] + [5, 6, 7], max_tokens=4,
                                 logprobs=True)
        return hits, out

    try:
        hits, out = asyncio.run(run())
        assert srv.page_mgr.snapshots_evicted >= 1
        assert srv.page_mgr.snapshot_hits == hits      # no hit: it was evicted
        assert _errs(srv, prompts[0] + [5, 6, 7], out).max() < 1e-4
    finally:
        srv.close()


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight chips share a layer: each computes the pairs that fall on its 2
    of 16 experts plus the shared expert. The eight parts, with the shared
    expert counted once, are the uncut 16-expert layer of the reference."""
    from ray_tpu.models.moe import MoEMLP
    cfg = LlamaConfig.solar_tiny(param_dtype=jnp.float32, dtype=jnp.float32)
    uncut = LlamaConfig.solar_tiny(param_dtype=jnp.float32, dtype=jnp.float32,
                                   experts_held=16)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 24, 64))
    params = MoEMLP(uncut).init(jax.random.PRNGKey(1), x)
    # the reference reads a layer: its norm is the identity here (scale 1 on
    # rows of unit RMS), and it adds the residual
    x = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.norm_eps)
    layer = {"moe": params["params"], "mlp_norm": {"scale": jnp.ones((64,))}}
    sizes = dict(SIZES, experts_first=0)
    want = reference.moe_block(x[0], layer, sizes) - x[0]
    shared = reference.moe_block(
        x[0], {**layer, "moe": {**params["params"], **{
            k: jnp.zeros_like(params["params"][k])
            for k in ("w_gate", "w_up", "w_down")}}}, sizes) - x[0]
    total = jnp.zeros_like(want)
    for share in range(8):
        held = {k: params["params"][k][2 * share:2 * share + 2]
                for k in ("w_gate", "w_up", "w_down")}
        part_cfg = LlamaConfig.solar_tiny(
            param_dtype=jnp.float32, dtype=jnp.float32, experts_held=2,
            experts_first=2 * share)
        part = MoEMLP(part_cfg).apply(
            {"params": {**params["params"], **held}}, x)[0]
        # the reference's own share, the same experts: they agree part by part
        ref_part = reference.moe_block(
            x[0], {**layer, "moe": {**params["params"], **held}},
            dict(sizes, experts_first=2 * share)) - x[0]
        np.testing.assert_allclose(part, ref_part, atol=1e-5)
        total = total + part
    np.testing.assert_allclose(total - 7 * shared, want, atol=1e-5)
    assert float(jnp.abs(want - shared).max()) > 1e-3   # the routed part is there


def test_forward_without_a_cache_is_the_reference(server):
    """The uncached forward (training's, `model.init`'s) starts every
    sequence from a zero state: the same logits as the reference."""
    rng = np.random.default_rng(3)
    toks = rng.integers(0, 256, 50)
    logits, _ = server.model.apply(server.params, jnp.asarray(toks)[None])
    want = reference.logits_of(server.params, toks, SIZES, 50)
    np.testing.assert_allclose(logits[0], want, atol=1e-4)


def test_what_such_a_model_cannot_do_says_so():
    with pytest.raises(ValueError, match="paged=True"):
        LLMServer(LLMConfig(preset="solar_tiny", paged=False))
    from ray_tpu.serve.pd import PrefillServer
    srv = PrefillServer(LLMConfig(preset="solar_tiny", paged=True,
                                  max_batch_slots=2, max_seq_len=64,
                                  page_size=8))
    try:   # the hand-off carries pages, not state: asked for, it says so
        with pytest.raises(NotImplementedError, match="recurrent state"):
            asyncio.run(srv.prefill_begin([1, 2, 3]))
    finally:
        srv.close()
