"""The gated delta rule (ops/linear_attention.py): the chunked program against
the token-by-token recurrence of the benchmark's reference, the decode update
against one step of it, the carried convolution."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.linear_attention import (causal_conv, kda_chunked,
                                          kda_recurrent, kda_step)


def _reference(q, k, v, g, beta, state):
    """`perfbench/references/solar_open2.py`'s recurrence, one row: plain
    numpy in float64, nothing of the program."""
    q, k, v, g, beta, s = (np.asarray(a, np.float64)
                           for a in (q, k, v, g, beta, state))
    out = []
    for t in range(q.shape[0]):
        s = s * np.exp(g[t])[..., None]
        u = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", s, k[t]))
        s = s + k[t][..., None] * u[:, None, :]
        out.append(np.einsum("hkv,hk->hv", s, q[t]))
    return np.stack(out), s


def _inputs(seed, b, t, h=3, dk=16, dv=8, beta_shift=0.0, fast_decay=0.3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (b, t, h, dk), minval=np.log(1e-4),
                                    maxval=np.log(fast_decay)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)) + beta_shift)
    state = jax.random.normal(ks[5], (b, h, dk, dv))
    return q, k, v, g, beta, state


@pytest.mark.parametrize("t", [1, 17, 64, 65, 150, 256])
def test_chunked_equals_the_recurrence_from_a_carried_state(t):
    q, k, v, g, beta, s0 = _inputs(t, 2, t)
    o, s = kda_chunked(q, k, v, g, beta, s0)
    for b in range(2):
        want_o, want_s = _reference(q[b], k[b], v[b], g[b], beta[b], s0[b])
        np.testing.assert_allclose(o[b], want_o, atol=2e-5)
        np.testing.assert_allclose(s[b], want_s, atol=2e-5)


def test_beta_near_two_and_fast_decay():
    # beta in (1.99, 2): I - beta k k^T reflects; decay down to exp(-1) a token
    q, k, v, g, beta, s0 = _inputs(3, 1, 192, beta_shift=10.0, fast_decay=1.0)
    assert float(beta.min()) > 1.99
    o, s = kda_chunked(q, k, v, g, beta, s0)
    want_o, want_s = _reference(q[0], k[0], v[0], g[0], beta[0], s0[0])
    np.testing.assert_allclose(o[0], want_o, atol=1e-4)
    np.testing.assert_allclose(s[0], want_s, atol=1e-4)


def test_padded_bucket_leaves_the_state_at_true_end():
    q, k, v, g, beta, s0 = _inputs(5, 2, 128)
    n_valid = jnp.array([100, 37])
    o, s = kda_chunked(q, k, v, g, beta, s0, n_valid=n_valid)
    for b, n in enumerate((100, 37)):
        want_o, want_s = _reference(q[b, :n], k[b, :n], v[b, :n], g[b, :n],
                                    beta[b, :n], s0[b])
        np.testing.assert_allclose(o[b, :n], want_o, atol=2e-5)
        np.testing.assert_allclose(s[b], want_s, atol=2e-5)
    assert bool(jnp.isfinite(o).all())


def test_chunks_carry_the_state_from_call_to_call():
    q, k, v, g, beta, s0 = _inputs(7, 1, 200)
    whole_o, whole_s = kda_chunked(q, k, v, g, beta, s0)
    cut = 72
    o1, s1 = kda_chunked(q[:, :cut], k[:, :cut], v[:, :cut], g[:, :cut],
                         beta[:, :cut], s0)
    o2, s2 = kda_chunked(q[:, cut:], k[:, cut:], v[:, cut:], g[:, cut:],
                         beta[:, cut:], s1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), whole_o, atol=2e-5)
    np.testing.assert_allclose(s2, whole_s, atol=2e-5)


def test_decode_update_is_one_step_of_the_recurrence():
    q, k, v, g, beta, s0 = _inputs(11, 3, 1)
    valid = jnp.array([True, False, True])
    o, s = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s0, valid)
    for b in range(3):
        want_o, want_s = _reference(q[b], k[b], v[b], g[b], beta[b], s0[b])
        np.testing.assert_allclose(o[b], want_o[0], atol=1e-5)
        # a row that does not decode this step keeps its state
        np.testing.assert_allclose(s[b], want_s if valid[b] else s0[b],
                                   atol=1e-5)
    o_all, s_all = kda_recurrent(q, k, v, g, beta, s0)
    np.testing.assert_allclose(o_all[:, 0], o, atol=1e-6)


def test_conv_carries_its_last_inputs():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 40, 12))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 12))
    zeros = jnp.zeros((2, 3, 12))
    whole, tail = causal_conv(x, zeros, w)
    want = sum(jnp.pad(x, ((0, 0), (3, 0), (0, 0)))[:, j:j + 40] * w[j]
               for j in range(4))
    np.testing.assert_allclose(whole, want, atol=1e-6)
    np.testing.assert_allclose(tail, x[:, -3:])
    # in two calls, the second padded: its tail is the inputs before n_valid
    y1, c1 = causal_conv(x[:, :25], zeros, w)
    padded = jnp.concatenate([x[:, 25:], jnp.full((2, 9, 12), 7.0)], 1)
    y2, c2 = causal_conv(padded, c1, w, n_valid=jnp.array([15, 15]))
    np.testing.assert_allclose(jnp.concatenate([y1, y2[:, :15]], 1), whole,
                               atol=1e-6)
    np.testing.assert_allclose(c2, x[:, -3:])
    # fewer real inputs than the window: the older ones come from `carried`
    _, c3 = causal_conv(padded, c1, w, n_valid=jnp.array([1, 2]))
    np.testing.assert_allclose(c3[0], jnp.concatenate([c1[0, 1:], x[0, 25:26]]))
    np.testing.assert_allclose(c3[1], jnp.concatenate([c1[1, 2:], x[1, 25:27]]))


def test_decode_kernel_equals_the_recurrence():
    """The pallas form of the decode update (the TPU's path), interpreted
    here: heads in blocks of 8, a head's state of whole 128 x 128 tiles."""
    q, k, v, g, beta, s0 = _inputs(13, 3, 1, h=16, dk=128, dv=128)
    valid = jnp.array([True, False, True])
    o, s = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s0, valid,
                    interpret=True)
    plain_o, plain_s = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                                s0, valid)
    np.testing.assert_allclose(o, plain_o, atol=1e-5)
    np.testing.assert_allclose(s, plain_s, atol=1e-5)
    for b in range(3):
        want_o, want_s = _reference(q[b], k[b], v[b], g[b], beta[b], s0[b])
        np.testing.assert_allclose(o[b], want_o[0], atol=1e-5)
        np.testing.assert_allclose(s[b], want_s if valid[b] else s0[b],
                                   atol=1e-5)
