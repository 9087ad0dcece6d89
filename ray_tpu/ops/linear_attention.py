"""Gated delta-rule linear attention with a per-channel decay (Kimi Delta
Attention, arXiv:2510.26692): a recurrence over a fixed state a head, as a
chunked program for prefill and a one-token update for decode, and the short
causal depthwise convolution that feeds it.

For one head with keys of `dk` and values of `dv`, the state S [dk, dv] moves
a token at a time:

    S_t = (I - beta_t k_t k_t^T) diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with alpha_t = exp(g_t) in (0, 1]^dk the decay of each key channel and
beta_t in (0, 2) the write strength. The state is the whole memory of the
sequence: its size does not grow with the context, so a serving slot holds
one a layer where full attention holds keys and values a token.

The chunked form (`kda_chunked`). With u_t = beta_t (v_t - S_{t-1}^T
diag(alpha_t) k_t) the update is S_t = diag(alpha_t) S_{t-1} + k_t u_t^T, so
inside a chunk of C tokens that starts from S_0, with G_t the running sum of
g and k+_t = k_t exp(G_t), k-_t = k_t exp(-G_t), q+_t = q_t exp(G_t):

    (I + diag(beta) A) U = diag(beta) (V - K+ S_0),  A_ti = k+_t . k-_i, i < t
    O = Q+ S_0 + tril(Q+ K-^T) U
    S_C = diag(exp(G_C)) S_0 + (K- exp(G_C))^T U

What does not depend on S_0 (A, the triangular inverse T, T V, T K+, the
causal Q+ K-^T) is computed for all chunks at once; a `lax.scan` over the
chunks then carries the state through three products a chunk. The exponents
are taken about the chunk's middle, so the two factors of a product stay
within exp(C/2 x |g|) of 1. Everything here is f32: the state and its update
are held in f32 whatever the activations' type.

Tokens past `n_valid` (the padding of a prefill bucket, a slot that does not
decode this step) take beta = 0 and g = 0: they write nothing and decay
nothing, so the state a call returns is the state after `n_valid` tokens.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
_HI = jax.lax.Precision.HIGHEST
_HEADS_A_BLOCK = 8      # heads of one row a grid step of the decode kernel


def causal_conv(x, carried, weight, n_valid=None, bias=None,
                scope: str = "kda_conv"):
    """Causal depthwise convolution over time whose last `W - 1` inputs are
    carried. x [B, T, Ch]; carried [B, W - 1, Ch]: the inputs before x[:, 0];
    weight [W, Ch]; n_valid [B] or None: how many of the T inputs are real;
    bias [Ch] or None. y_t = sum_j weight[j] x_{t - (W - 1) + j} (+ bias).
    Returns (y [B, T, Ch], the W - 1 inputs that precede position n_valid,
    in `carried`'s type)."""
    w = weight.shape[0]
    t = x.shape[1]
    with jax.named_scope(scope):
        window = jnp.concatenate([carried.astype(x.dtype), x], axis=1)
        y = sum(window[:, j:j + t] * weight[j].astype(x.dtype)
                for j in range(w))
        if bias is not None:
            y = y + bias.astype(x.dtype)
        if n_valid is None:
            tail = window[:, t:]
        else:
            tail = jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(
                row, n, w - 1, axis=0))(window, n_valid)
    return y, tail.astype(carried.dtype)


def _step_kernel(valid_ref, q_ref, k_ref, g_ref, v_ref, beta_ref, s_ref,
                 o_ref, s_out_ref):
    """One row's block of heads: the state is read once and written once.
    q, k, g [1, hb, dk]; v [1, hb, dv]; beta [1, hb, 1]; state
    [1, hb, dk, dv]. With a = exp(g): S' = diag(a) S + k u^T where
    u = beta (v - S^T (a k)), and o = S'^T q = S^T (a q) + (k . q) u, so the
    two products with the state are over the state as it was read. A key
    channel lies along the state's sublanes: a, k, a k and a q are
    transposed once a block, and a head takes its column."""
    live = valid_ref[pl.program_id(0)] > 0
    q, k, v = q_ref[0], k_ref[0], v_ref[0]               # [hb, dk | dv]
    a = jnp.exp(g_ref[0])
    beta = beta_ref[0]                                    # [hb, 1]
    kq = jnp.sum(k * q, axis=-1, keepdims=True)           # [hb, 1]
    a_t, k_t, ak_t, aq_t = a.T, k.T, (a * k).T, (a * q).T  # [dk, hb]
    for h in range(q.shape[0]):
        s = s_ref[0, h]                                   # [dk, dv]
        col = lambda x: x[:, h:h + 1]                     # [dk, 1]
        u = beta[h:h + 1] * (v[h:h + 1] - jnp.sum(
            s * col(ak_t), axis=0, keepdims=True))        # [1, dv]
        o_ref[0, h:h + 1] = (jnp.sum(s * col(aq_t), axis=0, keepdims=True)
                             + kq[h:h + 1] * u)
        s_out_ref[0, h] = jnp.where(live, s * col(a_t) + col(k_t) * u, s)


def _kda_step_kernel(q, k, v, g, beta, state, valid, interpret=False):
    """`kda_step` as one pallas kernel: grid (rows, blocks of heads), the
    state aliased to its output, so a step moves each row's state once in
    and once out (XLA's own fusions of the same update passed over it five
    or six times: trace of the v5e, PR 35)."""
    b, h, dk = k.shape
    dv = v.shape[-1]
    hb = _HEADS_A_BLOCK
    row = lambda w: pl.BlockSpec((1, hb, w), lambda i, j, _: (i, j, 0))
    whole = pl.BlockSpec((1, hb, dk, dv), lambda i, j, _: (i, j, 0, 0))
    return pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, h // hb),
            in_specs=[row(dk), row(dk), row(dk), row(dv), row(1), whole],
            out_specs=[row(dv), whole]),
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={6: 1},
        interpret=interpret,
        name="kda_decode",
    )(valid.astype(jnp.int32), q, k, g, v, beta[..., None], state)


def kda_step(q, k, v, g, beta, state, valid=None, interpret=None):
    """One token a row (decode). q, k, g [B, H, dk]; v [B, H, dv]; beta
    [B, H]; state [B, H, dk, dv] f32; valid [B] bool or None: rows whose
    state moves. Returns (o [B, H, dv] f32, state'). On the TPU (and where
    `interpret` says so) a pallas kernel, where the heads divide into its
    blocks and a head's state is whole tiles; XLA's operations elsewhere."""
    f32 = jnp.float32
    b, h, dk = k.shape
    kernel = interpret is not None or jax.default_backend() == "tpu"
    if (kernel and h % _HEADS_A_BLOCK == 0 and dk % 128 == 0
            and v.shape[-1] % 128 == 0):
        q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
        if valid is None:
            valid = jnp.ones((b,), bool)
        return _kda_step_kernel(q, k, v, g, beta, state.astype(f32), valid,
                                interpret=bool(interpret))
    with jax.named_scope("kda_decode"):
        f32 = jnp.float32
        q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
        s = state * jnp.exp(g)[..., None]
        u = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k,
                                              precision=_HI))
        s = s + k[..., None] * u[..., None, :]
        o = jnp.einsum("bhkv,bhk->bhv", s, q, precision=_HI)
        if valid is not None:
            s = jnp.where(valid[:, None, None, None], s, state)
    return o, s


_INVERSE_BASE = 16      # rows of a diagonal block inverted row by row


def _unit_lower_inverse(low):
    """(I + L)^-1 for strictly lower-triangular L [..., C, C]. The diagonal
    blocks of `_INVERSE_BASE` rows are inverted row by row (forward
    substitution: row i is e_i - L[i] X; exact, and stable where a Neumann
    product's powers of L cancel, beta near 2), all of them at once; pairs of
    blocks are then joined by [[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C
    A^-1, B^-1]] until one block is left. Sixteen steps and a few small
    products where the plain row loop took C steps of a launch each (6.6% of
    the device's time in the first trace on the v5e, PR 35)."""
    c = low.shape[-1]
    lead = low.shape[:-2]
    base = _INVERSE_BASE if c % _INVERSE_BASE == 0 else c
    n = c // base
    cut = low.reshape(lead + (n, base, n, base))
    diag = jnp.stack([cut[..., i, :, i, :] for i in range(n)], axis=-3)
    eye = jnp.eye(base, dtype=low.dtype)

    def row(i, x):
        new = eye[i] - jnp.einsum("...j,...jk->...k", diag[..., i, :], x,
                                  precision=_HI)
        return x.at[..., i, :].set(new)

    inv = jax.lax.fori_loop(0, base, row, jnp.zeros_like(diag))  # [.., n, b, b]
    size = base
    while size < c:
        pairs = c // (2 * size)
        cut = low.reshape(lead + (pairs, 2, size, pairs, 2, size))
        below = jnp.stack([cut[..., p, 1, :, p, 0, :] for p in range(pairs)],
                          axis=-3)                       # C of every pair
        a, b = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        corner = -jnp.einsum("...ij,...jk,...kl->...il", b, below, a,
                             precision=_HI)
        inv = jnp.concatenate([
            jnp.concatenate([a, jnp.zeros_like(a)], axis=-1),
            jnp.concatenate([corner, b], axis=-1)], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


def kda_chunked(q, k, v, g, beta, state, n_valid=None, chunk: int = CHUNK):
    """A stretch of T tokens a row (prefill), from `state` on. q, k, g
    [B, T, H, dk]; v [B, T, H, dv]; beta [B, T, H]; state [B, H, dk, dv] f32;
    n_valid [B] or None. Returns (o [B, T, H, dv] f32, the state after
    n_valid tokens). `q` comes in scaled, `q` and `k` normalised."""
    with jax.named_scope("kda_prefill"):
        f32 = jnp.float32
        b, t, h, dk = k.shape
        dv = v.shape[-1]
        q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
        if n_valid is not None:
            real = jnp.arange(t)[None] < n_valid[:, None]          # [B, T]
            g = jnp.where(real[..., None, None], g, 0.0)
            beta = jnp.where(real[..., None], beta, 0.0)
        n = -(-t // chunk)
        pad = n * chunk - t
        if pad:
            q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                          for a in (q, k, v, g))
            beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
        # [B, n, H, C, .]: chunks and heads are batch dimensions
        cut = lambda a: a.reshape(b, n, chunk, h, -1).swapaxes(2, 3)
        q, k, v, g = cut(q), cut(k), cut(v), cut(g)
        beta = beta.reshape(b, n, chunk, h).swapaxes(2, 3)         # [B,n,H,C]
        gc = jnp.cumsum(g, axis=-2)
        mid = gc[..., chunk // 2 - 1:chunk // 2, :]
        k_up, k_dn = k * jnp.exp(gc - mid), k * jnp.exp(mid - gc)
        q_up = q * jnp.exp(gc - mid)
        lower = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
        a = jnp.einsum("...tk,...ik->...ti", k_up, k_dn, precision=_HI)
        a = jnp.where(lower, a, 0.0) * beta[..., None]
        tri = _unit_lower_inverse(a) * beta[..., None, :]           # T
        w = jnp.einsum("...ti,...ik->...tk", tri, k_up, precision=_HI)
        uv = jnp.einsum("...ti,...iv->...tv", tri, v, precision=_HI)
        aqk = jnp.einsum("...tk,...ik->...ti", q_up, k_dn, precision=_HI)
        aqk = jnp.where(lower | jnp.eye(chunk, dtype=bool), aqk, 0.0)
        end = gc[..., -1:, :]                                       # G_C
        k_end = k * jnp.exp(end - gc)                               # K- e^G_C
        decay = jnp.exp(end[..., 0, :])                             # [B,n,H,dk]

        # q+ and w carry exp(G - mid): S_0 wants exp(G), so the state a
        # chunk sees is scaled by exp(mid) on its key channels
        scale = jnp.exp(mid[..., 0, :])[..., None]                  # [B,n,H,dk,1]

        def step(s, xs):
            w, uv, q_up, aqk, k_end, decay, sc = xs
            s_mid = s * sc
            u = uv - jnp.einsum("bhtk,bhkv->bhtv", w, s_mid, precision=_HI)
            o = (jnp.einsum("bhtk,bhkv->bhtv", q_up, s_mid, precision=_HI)
                 + jnp.einsum("bhti,bhiv->bhtv", aqk, u, precision=_HI))
            s = decay[..., None] * s + jnp.einsum(
                "bhtk,bhtv->bhkv", k_end, u, precision=_HI)
            return s, o

        lead = lambda x: jnp.moveaxis(x, 1, 0)     # the scan runs over chunks
        state, o = jax.lax.scan(
            step, state.astype(f32),
            tuple(lead(x) for x in (w, uv, q_up, aqk, k_end, decay, scale)))
        o = jnp.moveaxis(o, 0, 1).swapaxes(2, 3).reshape(b, n * chunk, h, dv)
    return o[:, :t], state


def kda_recurrent(q, k, v, g, beta, state):
    """The recurrence itself, a token at a time (`kda_step` under a scan):
    what the chunked form has to equal. Same arguments as `kda_chunked`."""
    def step(s, xs):
        o, s = kda_step(*xs, s)
        return s, o
    state, o = jax.lax.scan(step, state.astype(jnp.float32), tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state
