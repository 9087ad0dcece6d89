"""Ops numerics on the CPU mesh (SURVEY.md §4: pallas == XLA reference;
ring == dense; losses vs naive python)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention import apply_rope, decode_attention, mha_reference
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.ops import losses
from ray_tpu.parallel.mesh import local_cpu_mesh


def _qkv(B=2, T=128, H=4, Kh=2, D=32, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(ks[0], (B, T, H, D), dtype),
            jax.random.normal(ks[1], (B, T, Kh, D), dtype),
            jax.random.normal(ks[2], (B, T, Kh, D), dtype))


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_fwd_matches_reference(self, causal):
        q, k, v = _qkv()
        ref = mha_reference(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_kv=64)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    def test_grads_match_reference(self):
        q, k, v = _qkv(T=128)
        gf = jax.grad(lambda *a: jnp.sum(
            flash_attention(*a, causal=True, block_q=64, block_kv=64) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: jnp.sum(
            mha_reference(*a, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(a, b, atol=5e-4)

    def test_mqa(self):
        q, k, v = _qkv(H=4, Kh=1)
        np.testing.assert_allclose(
            flash_attention(q, k, v, block_q=64, block_kv=64),
            mha_reference(q, k, v), atol=2e-5)

    def test_lengths_that_do_not_tile_raise(self):
        """No O(T^2) path hides behind the kernel's name: callers with
        ragged lengths pad, or call mha_reference themselves."""
        q, k, v = _qkv(T=96)
        with pytest.raises(ValueError, match="don't tile"):
            flash_attention(q, k, v, block_q=64, block_kv=64)

    def test_runs_per_shard_under_a_context_mesh(self):
        """Under jax.set_mesh the kernel is shard_mapped over the data axes
        (batch) and tp (heads): same numbers, outputs left sharded."""
        from ray_tpu.parallel.mesh import make_mesh
        q, k, v = _qkv(B=4, T=64, H=4, Kh=2, D=16)
        ref = mha_reference(q, k, v)
        mesh = make_mesh({"fsdp": 2, "tp": 2}, devices=jax.devices()[:4])
        with jax.set_mesh(mesh):
            out = jax.jit(lambda *a: flash_attention(
                *a, block_q=64, block_kv=64))(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-5)
        assert out.sharding.spec == P("fsdp", None, "tp")


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        mesh = local_cpu_mesh(4, {"sp": 4})
        q, k, v = _qkv(B=2, T=64, H=4, Kh=2, D=16)
        ring = shard_map(
            lambda a, b, c: ring_attention(a, b, c, axis_name="sp", causal=causal),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"))(q, k, v)
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(ring, ref, atol=2e-5)


class TestRope:
    def test_norm_preserved(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 4, 32))
        pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
        y = apply_rope(x, pos)
        np.testing.assert_allclose(
            jnp.linalg.norm(y, axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5)

    def test_relative_property(self):
        # <rope(q,m), rope(k,n)> depends only on m-n
        d = 32
        q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, d))
        k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, d))
        def dot_at(m, n):
            qm = apply_rope(q, jnp.array([[m]]))
            kn = apply_rope(k, jnp.array([[n]]))
            return float(jnp.sum(qm * kn))
        assert abs(dot_at(5, 3) - dot_at(10, 8)) < 1e-4


class TestDecodeAttention:
    def test_masked_cache_matches_dense(self):
        B, S, H, Kh, D = 2, 32, 4, 2, 16
        q = jax.random.normal(jax.random.PRNGKey(0), (B, 1, H, D))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Kh, D))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Kh, D))
        n = 20  # tokens already in cache; q is the token at position n
        out = decode_attention(q, k, v, jnp.full((B,), n, jnp.int32))
        ref = mha_reference(q, k[:, :n + 1], v[:, :n + 1], causal=False)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_chunked_queries_causal(self):
        """T>1 chunk: query j only sees cache slots ≤ lengths+j."""
        B, S, T, H, Kh, D = 1, 16, 4, 2, 2, 8
        q = jax.random.normal(jax.random.PRNGKey(0), (B, T, H, D))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Kh, D))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Kh, D))
        n = 5
        out = decode_attention(q, k, v, jnp.array([n], jnp.int32))
        for j in range(T):
            ref = mha_reference(q[:, j:j + 1], k[:, :n + j + 1], v[:, :n + j + 1],
                                causal=False)
            np.testing.assert_allclose(out[:, j:j + 1], ref, atol=1e-5)


class TestLosses:
    def test_cross_entropy_uniform(self):
        logits = jnp.zeros((4, 8, 16))
        labels = jnp.zeros((4, 8), jnp.int32)
        loss, m = losses.cross_entropy(logits, labels)
        np.testing.assert_allclose(loss, np.log(16), rtol=1e-5)
        assert m["tokens"] == 32

    def test_cross_entropy_mask(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 8))
        labels = jnp.ones((2, 4), jnp.int32)
        mask = jnp.array([[1, 1, 0, 0], [1, 0, 0, 0]], jnp.float32)
        loss, m = losses.cross_entropy(logits, labels, mask=mask)
        # equals mean over the 3 unmasked tokens
        full = -jax.nn.log_softmax(logits)[..., 1]
        expect = (full[0, 0] + full[0, 1] + full[1, 0]) / 3
        np.testing.assert_allclose(loss, expect, rtol=1e-5)

    def test_gae_vs_naive(self):
        T = 7
        rng = np.random.RandomState(0)
        r = rng.randn(T).astype(np.float32)
        val = rng.randn(T + 1).astype(np.float32)
        done = np.array([0, 0, 1, 0, 0, 0, 0], np.float32)
        gamma, lam = 0.9, 0.8
        adv, tgt = losses.gae(jnp.array(r), jnp.array(val), jnp.array(done), gamma, lam)
        expect = np.zeros(T, np.float32)
        acc = 0.0
        for t in reversed(range(T)):
            nd = 1.0 - done[t]
            delta = r[t] + gamma * val[t + 1] * nd - val[t]
            acc = delta + gamma * lam * nd * acc
            expect[t] = acc
        np.testing.assert_allclose(adv, expect, rtol=1e-4)
        np.testing.assert_allclose(tgt, expect + val[:-1], rtol=1e-4)

    def test_vtrace_on_policy_is_gae_lambda1(self):
        # With rho=c=1 (on-policy) v-trace targets equal TD(lambda=1) returns.
        T = 5
        rng = np.random.RandomState(1)
        r = jnp.array(rng.randn(T), jnp.float32)
        val = jnp.array(rng.randn(T + 1), jnp.float32)
        done = jnp.zeros(T)
        lp = jnp.zeros(T)
        out = losses.vtrace(lp, lp, r, val, done, gamma=0.9)
        adv, tgt = losses.gae(r, val, done, gamma=0.9, lam=1.0)
        np.testing.assert_allclose(out.vs, tgt, rtol=1e-4)

    def test_ppo_surrogate_clip(self):
        lp = jnp.array([0.0, jnp.log(2.0)])
        old = jnp.zeros(2)
        adv = jnp.array([1.0, 1.0])
        loss, frac = losses.ppo_surrogate(lp, old, adv, clip=0.2)
        # ratios [1, 2] → clipped to [1, 1.2] → loss = -mean = -1.1
        np.testing.assert_allclose(loss, -1.1, rtol=1e-5)
        np.testing.assert_allclose(frac, 0.5)

    def test_huber(self):
        x = jnp.array([-2.0, 0.5, 2.0])
        np.testing.assert_allclose(losses.huber(x), [1.5, 0.125, 1.5])


class TestChunkedCrossEntropy:
    def test_matches_full_cross_entropy(self):
        from ray_tpu.ops.losses import chunked_cross_entropy, cross_entropy
        key = jax.random.PRNGKey(0)
        B, T, D, V = 2, 128, 32, 97
        hidden = jax.random.normal(key, (B, T, D), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (D, V), jnp.float32) * 0.05
        labels = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, V)
        full, m_full = cross_entropy(hidden @ w, labels)
        chunked, m_chunk = chunked_cross_entropy(hidden, w, labels, chunk_size=32)
        np.testing.assert_allclose(chunked, full, rtol=1e-5)
        np.testing.assert_allclose(m_chunk["accuracy"], m_full["accuracy"], rtol=1e-5)

    def test_grads_match(self):
        from ray_tpu.ops.losses import chunked_cross_entropy, cross_entropy
        key = jax.random.PRNGKey(3)
        B, T, D, V = 2, 64, 16, 31
        hidden = jax.random.normal(key, (B, T, D), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(4), (D, V), jnp.float32) * 0.1
        labels = jax.random.randint(jax.random.PRNGKey(5), (B, T), 0, V)
        g_full = jax.grad(lambda h, w: cross_entropy(h @ w, labels)[0], argnums=(0, 1))(hidden, w)
        g_chunk = jax.grad(lambda h, w: chunked_cross_entropy(h, w, labels, chunk_size=16)[0],
                           argnums=(0, 1))(hidden, w)
        for a, b in zip(g_chunk, g_full):
            np.testing.assert_allclose(a, b, atol=1e-5)

    def test_model_return_hidden_consistent(self):
        from ray_tpu.models.llama import Llama, LlamaConfig
        cfg = LlamaConfig.tiny(max_seq_len=32)
        model = Llama(cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, cfg.vocab_size)
        params = model.init(jax.random.PRNGKey(1), tokens)
        logits, _ = model.apply(params, tokens)
        hidden, _ = model.apply(params, tokens, return_hidden=True)
        w = params["params"]["lm_head"]["kernel"]
        np.testing.assert_allclose(
            np.asarray(hidden.astype(jnp.float32)) @ np.asarray(w, dtype=np.float32),
            logits, atol=2e-2)
