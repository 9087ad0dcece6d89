"""`perfbench/reference.py` against the program's `Llama` at `tiny` and
`moe_tiny` sizes on the CPU, both in float32: the reference is written from
the published equations and shares nothing with the program but the parameter
tree, so the two agree only if both are the same model."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import reference
from perfbench.builders import llama_family
from ray_tpu.models.llama import Llama, LlamaConfig


def _setup(preset):
    config = {"num_local_experts": 8} if preset == "moe_tiny" else {}
    sizes = llama_family.model_sizes(config, rehearse=True)
    cfg = getattr(LlamaConfig, preset)(dtype=jnp.float32, param_dtype=jnp.float32)
    if cfg.n_experts:    # dropless, as the server runs it
        cfg = dataclasses.replace(cfg, capacity_factor=cfg.n_experts / cfg.moe_top_k)
    assert (cfg.d_model, cfg.n_layers, cfg.n_experts) == (
        sizes["d_model"], sizes["n_layers"], sizes["n_experts"])
    model = Llama(cfg)
    params = model.init(llama_family.seed_key(2**31 + 7), jnp.zeros((1, 8), jnp.int32))
    return model, params, sizes


@pytest.mark.parametrize("preset", ["tiny", "moe_tiny"])
def test_logprobs_match_the_program(preset):
    model, params, sizes = _setup(preset)
    tokens = np.random.default_rng(0).integers(0, sizes["vocab"], 48)
    with jax.default_matmul_precision("highest"):
        logits, _ = model.apply(params, jnp.asarray(tokens[None, :-1]))
    logp = jax.nn.log_softmax(logits[0], axis=-1)
    want = np.asarray(logp[np.arange(47), tokens[1:]])[-9:]
    got = np.asarray(reference.logprobs_of(params, tokens, sizes, 9))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("preset", ["tiny", "moe_tiny"])
def test_loss_matches_the_program(preset):
    model, params, sizes = _setup(preset)
    batch = np.random.default_rng(1).integers(0, sizes["vocab"], (3, 33))
    with jax.default_matmul_precision("highest"):
        logits, _ = model.apply(params, jnp.asarray(batch[:, :-1]))
    logp = jax.nn.log_softmax(logits, axis=-1)
    want = -float(jnp.mean(jnp.take_along_axis(
        logp, jnp.asarray(batch[:, 1:, None]), axis=-1)))
    assert float(reference.loss_of(params, batch, sizes)) == pytest.approx(want, abs=2e-5)


def test_a_dropped_layer_is_seen():
    """The tolerance is tight enough that leaving out part of the model
    fails: one layer fewer moves the log-probabilities far more."""
    model, params, sizes = _setup("tiny")
    tokens = np.random.default_rng(2).integers(0, sizes["vocab"], 40)
    full = np.asarray(reference.logprobs_of(params, tokens, sizes, 9))
    cut = np.asarray(reference.logprobs_of(
        params, tokens, dict(sizes, n_layers=1), 9))
    assert np.max(np.abs(full - cut)) > 1e-3


@pytest.mark.parametrize("preset", ["tiny", "moe_tiny"])
def test_the_control_moves_what_a_lower_precision_moves(preset):
    """`weights_as` is the tolerances' control (`run.py --control-dtype`, read
    on the chip at the real widths, where the logits are ten times wider than
    at these sizes): 8-bit weights move the log-probabilities many times
    further than the bf16 the configurations state."""
    _, params, sizes = _setup(preset)
    tokens = np.random.default_rng(3).integers(0, sizes["vocab"], 60)
    want = np.asarray(reference.logprobs_of(params, tokens, sizes, 18))
    err = {dt: np.median(np.abs(np.asarray(reference.logprobs_of(
        params, tokens, sizes, 18, weights_as=dt)) - want))
        for dt in ("bfloat16", "float8_e4m3fn")}
    assert 0 < 8 * err["bfloat16"] < err["float8_e4m3fn"]


def test_seed_key_takes_seeds_past_int32():
    a, b = llama_family.seed_key(2**31 + 5), llama_family.seed_key(5)
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(b))
