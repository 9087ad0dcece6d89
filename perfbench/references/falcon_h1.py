"""The plain reference for Falcon-H1 (`falcon_h1`): the forward pass in
straightforward `jax.numpy` and float32, written from the block's equations
(the configuration's file lists what the published config does not fix, under
`assumed`). `m` is the configuration's `multipliers` group, the published muP
numbers.

Embedding: x = E[token] * m.embedding. Every block alike, n = RMSNorm_in(x):

    x = x + m.ssm_out * Mixer(n) + m.attention_out * Attn(n * m.attention_in)
    x = x + MLP(RMSNorm_ff(x))

Attn: q = h Wq [T, H, D], k = (h Wk) * m.key, v = h Wv [T, Kh, D]; rotary over
all D dimensions in the rotate-half form (pairs (i, i + D/2), frequency
theta^(-2i/D)) on q and k; o[t, a] = sum_{s <= t} softmax_s(q[t, a] .
k[s, kv(a)] / sqrt(D)) v[s, kv(a)], kv(a) = a // (H / Kh); out = concat(o) Wo.

Mixer (Mamba-2): zxbcdt = ((n * m.ssm_in) W_in) * mup, mup the five
`m.ssm` numbers spread over the segments z [H_s P], x [H_s P], B [G N],
C [G N], dt [H_s]. c = silu(conv4([x | B | C]) + bias): a causal depthwise
convolution of width 4 over time, y_t = sum_j w[j] c_{t-3+j}, zeros before the
sequence. dt = softplus(dt + dt_bias), A = -exp(A_log), a head each. Token by
token, from S_0 = 0 in R^{N x P} a head, the group's B and C:

    S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T,   y_t = S_t^T C_t + D x_t

THE RECURRENCE ITSELF, a `lax.scan` over tokens: not the program's chunked
form. Then y = RMSNorm_groups(y * silu(z)) (the gate first, then the norm over
each of the G groups of H_s P / G channels, a learned scale) and out = y W_out.

MLP: down(silu(gate(h) * m.mlp_gate) * up(h)) * m.mlp_down.

Then logits = (RMSNorm(x) W_head) * m.lm_head, an untied head. It reads the
program's parameter tree and imports nothing of the program.

Departures from the published description, each for memory only (it runs
beside 13.7 GB of weights and cache): one layer at a time, a layer's matrices
converted to float32 one at a time; attention's queries in blocks of
`_Q_BLOCK` against all keys; the FFN in stretches of `_T_BLOCK` tokens; the
head in blocks of `_HEAD_BLOCK` columns (its float32 copy whole would be
5.35 GB). Every matmul runs under `jax.default_matmul_precision("highest")`:
on a TPU a float32 matmul is bf16 passes otherwise.
"""

import functools

import jax
import jax.numpy as jnp

_Q_BLOCK = 64         # query positions a block: [H, 64, T] f32 scores
_T_BLOCK = 1024       # tokens a stretch of the FFN
_HEAD_BLOCK = 8192    # columns of the head cast to f32 at a time


def _f32(x):
    return x.astype(jnp.float32)


def _as(w, dtype):
    """The weight as it is, or rounded to `dtype` (a lower precision's
    control) and back. Vectors (norm scales, biases, A_log, D) stay."""
    if dtype is None or w.ndim < 2:
        return w
    return w.astype(dtype).astype(w.dtype)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(scale)


@functools.partial(jax.jit, static_argnames=("weights_as",))
def _times(x, w, weights_as=None):
    """x @ (one matrix, converted here and nowhere kept)."""
    return x @ _f32(_as(w, weights_as))


def _rotate_half(x, theta):
    """x [T, heads, D] at positions 0 .. T - 1."""
    t, _, d = x.shape
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("static",))
def _attend(q, k, v, static):
    """Causal softmax attention: q [T, H, D], k, v [T, Kh, D] -> [T, H D]."""
    n_heads, n_kv, hd, theta = static
    t = q.shape[0]
    q, k = _rotate_half(q, theta), _rotate_half(k, theta)
    pad = -t % _Q_BLOCK
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, _Q_BLOCK, n_kv, n_heads // n_kv, hd)
    starts = jnp.arange(qp.shape[0]) * _Q_BLOCK

    def block(args):
        qb, start = args
        rows = start + jnp.arange(_Q_BLOCK)[:, None]
        causal = jnp.arange(t)[None, :] <= rows
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), -1)
        return jnp.einsum("kgqs,skd->qkgd", p, v).reshape(_Q_BLOCK, -1)

    out = jax.lax.map(block, (qp, starts))
    return out.reshape(-1, out.shape[-1])[:t]


@functools.partial(jax.jit, static_argnames=("static",))
def _mix(zxbcdt, mamba, static):
    """The mixer between its two projections: zxbcdt [T, width], the
    multipliers applied -> the gated, normed y [T, H_s P]."""
    h, p, n, g, eps = static
    t = zxbcdt.shape[0]
    inner, bc = h * p, g * n
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * bc], -1)
    conv_w = _f32(mamba["conv"])                                 # [W, Ch]
    w = conv_w.shape[0]
    window = jnp.concatenate([jnp.zeros((w - 1, xbc.shape[1])), xbc], 0)
    c = jax.nn.silu(sum(window[j:j + t] * conv_w[j] for j in range(w))
                    + _f32(mamba["conv_bias"]))
    x, b, cm = jnp.split(c, [inner, inner + bc], -1)
    x = x.reshape(t, h, p)
    b = jnp.repeat(b.reshape(t, g, n), h // g, axis=1)           # [T, H, N]
    cm = jnp.repeat(cm.reshape(t, g, n), h // g, axis=1)
    dt = jax.nn.softplus(dt + mamba["dt_bias"])                  # [T, H]
    a = -jnp.exp(mamba["A_log"])

    def token(s, args):
        x_t, b_t, c_t, dt_t = args
        s = (s * jnp.exp(dt_t * a)[:, None, None]
             + b_t[:, :, None] * (dt_t[:, None] * x_t)[:, None, :])
        return s, jnp.einsum("hnp,hn->hp", s, c_t) + mamba["D"][:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((h, n, p)), (x, b, cm, dt))
    y = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(t, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return y.reshape(t, inner) * mamba["norm"]


def _segment_multipliers(sizes):
    h, p = sizes["ssm_heads"], sizes["ssm_head_dim"]
    bc = sizes["ssm_groups"] * sizes["ssm_state"]
    return jnp.concatenate([
        jnp.full((w,), m, jnp.float32) for w, m in
        zip((h * p, h * p, bc, bc, h), sizes["multipliers"]["ssm"])])


def _rms(x):
    return float(jnp.sqrt(jnp.mean(x * x)))


def block(x, layer, sizes, weights_as=None, branches=("mixer", "attention"),
          probe=None):
    """One block over x [T, D]. `branches`: which of the two parallel
    branches are added (a test leaves one out to see that the check sees).
    `probe`: a list that is given the RMS of the stream coming in and of
    each branch as it is added to it."""
    m, eps = sizes["multipliers"], sizes["norm_eps"]
    t = x.shape[0]
    times = functools.partial(_times, weights_as=weights_as)
    norm = jax.jit(_rms_norm, static_argnums=2)
    n = norm(x, layer["attn_norm"]["scale"], eps)
    mixed = attended = jnp.zeros_like(x)
    if "mixer" in branches:
        mamba = layer["mamba"]
        zxbcdt = (times(n * m["ssm_in"], mamba["in_proj"]["kernel"])
                  * _segment_multipliers(sizes))
        y = _mix(zxbcdt, {k: v for k, v in mamba.items()
                          if k not in ("in_proj", "out_proj")},
                 (sizes["ssm_heads"], sizes["ssm_head_dim"],
                  sizes["ssm_state"], sizes["ssm_groups"], eps))
        mixed = m["ssm_out"] * times(y, mamba["out_proj"]["kernel"])
    if "attention" in branches:
        attn, hd = layer["attn"], sizes["head_dim"]
        a_in = n * m["attention_in"]
        q = times(a_in, attn["wq"]["kernel"]).reshape(t, -1, hd)
        k = (times(a_in, attn["wk"]["kernel"]) * m["key"]).reshape(t, -1, hd)
        v = times(a_in, attn["wv"]["kernel"]).reshape(t, -1, hd)
        o = _attend(q, k, v, (sizes["n_heads"], sizes["n_kv_heads"], hd,
                              float(sizes["rope_theta"])))
        attended = m["attention_out"] * times(o, attn["wo"]["kernel"])
    after, mlp, parts = x + mixed + attended, layer["mlp"], []
    for s in range(0, t, _T_BLOCK):
        h = norm(after[s:s + _T_BLOCK], layer["mlp_norm"]["scale"], eps)
        act = (jax.nn.silu(times(h, mlp["w_gate"]["kernel"]) * m["mlp_gate"])
               * times(h, mlp["w_up"]["kernel"]))
        parts.append(times(act, mlp["w_down"]["kernel"]) * m["mlp_down"])
    fed = jnp.concatenate(parts, 0)
    if probe is not None:
        probe.append({"stream": _rms(x), "mixer": _rms(mixed),
                      "attention": _rms(attended), "mlp": _rms(fed)})
    return after + fed


def hidden_states(params, tokens, sizes: dict, weights_as=None, **kw):
    """Final-norm hidden states [T, D] of one sequence of token ids [T].
    `weights_as`: every matrix rounded to that type first."""
    p = params["params"]
    tokens = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        x = (_f32(_as(p["embed"]["embedding"][tokens], weights_as))
             * sizes["multipliers"]["embedding"])
        for i in range(sizes["n_layers"]):
            x = block(x, p[f"layers_{i}"], sizes, weights_as, **kw)
        return jax.jit(_rms_norm, static_argnums=2)(
            x, p["final_norm"]["scale"], sizes["norm_eps"])


def logits_of(params, tokens, sizes: dict, n_last: int, weights_as=None, **kw):
    """The logits [n_last, V] that follow each of the last `n_last` tokens of
    `tokens` (one sequence)."""
    hidden = hidden_states(params, jnp.asarray(tokens, jnp.int32), sizes,
                           weights_as, **kw)[-n_last:]
    w_head = params["params"]["lm_head"]["kernel"]
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate([
            _times(hidden, w_head[:, c:c + _HEAD_BLOCK], weights_as)
            for c in range(0, w_head.shape[1], _HEAD_BLOCK)],
            axis=-1) * sizes["multipliers"]["lm_head"]


def logprobs_of(params, tokens, sizes: dict, n_last: int, weights_as=None,
                **kw):
    """log p(tokens[i] | tokens[:i]) for the last `n_last` tokens of one
    sequence: what a server that was given tokens[:-n_last] as the prompt and
    generated the rest must report, teacher-forced on its own tokens."""
    tokens = jnp.asarray(tokens, jnp.int32)
    logits = logits_of(params, tokens[:-1], sizes, n_last, weights_as, **kw)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, tokens[-n_last:, None], axis=-1)[:, 0]
