"""The plain reference for Solar-Open2-250B as one chip's share: the forward
pass in straightforward `jax.numpy` and float32, written from the layer's
equations (the configuration's file lists what the published config does not
fix, under `assumed`). Pre-norm residual blocks, RMSNorm with a learned
scale, no positions anywhere. Layer i is a FULL layer iff i % 4 == 0
(`full_attn_every`), else a LINEAR layer; every layer ends in the MoE.

Linear layer (Kimi Delta Attention, arXiv:2510.26692), h = RMSNorm(x):
1. c = conv4([h Wq | h Wk | h Wv]): a causal depthwise convolution of width 4
   over time on all channels, y_t = sum_j w[j] c_{t-3+j}, zeros before the
   sequence; q = l2norm(silu(c_q)) / sqrt(dk), k = l2norm(silu(c_k)),
   v = silu(c_v), a head at a time (H heads of dk = dv).
2. g = -exp(A_log[h]) softplus((h Wfa) Wfb + dt_bias) in R^{H x dk} (the
   log-decay of each key channel), alpha = exp(g); beta = 2 sigmoid(h Wb) in
   (0, 2)^H.
3. Token by token, from S_0 = 0 in R^{dk x dv}:
   S_t = (I - beta_t k_t k_t^T) diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,
   o_t = S_t^T q_t. THE RECURRENCE ITSELF, a `lax.scan` over tokens: not the
   program's chunked form.
4. x = x + ((RMSNorm_head(o) * sigmoid((h Wga) Wgb)) Wo.

Full layer: q = h Wq [T, H, D], k = h Wk, v = h Wv [T, Kh, D], no rotary;
o[t, a] = sum_{s <= t} softmax_s(q[t, a] . k[s, kv(a)] / sqrt(D)) v[s, kv(a)];
x = x + (concat(o) * sigmoid(h Wgate)) Wo.

MoE, h2 = RMSNorm(x): s = sigmoid(h2 Wr) over ALL `n_experts`, the `top_k`
largest, gates s_i / sum of the chosen s. THE SHARE: the bank holds the
experts `experts_first` .. `experts_first + held - 1` (held = the leading
dimension of the bank's weights: 40 of 320 in the benchmark's cut, all of
them in an uncut layer); a chosen expert that is held is applied (SwiGLU)
under its gate, the others are another chip's and add nothing here; plus the
shared SwiGLU expert on every token. x = x + that sum: this chip's part of
the layer, with nothing standing in for the absent chips, as in the program.

Then a final RMSNorm and an untied head over the vocabulary slice the file
holds. It reads the program's parameter tree and imports nothing of the
program.

Departures from the published description, each for memory only (it runs
beside 12.6 GB of weights and cache): one layer at a time; a linear layer in
stretches of `_T_BLOCK` tokens that carry the state and the convolution's
last 3 inputs (the recurrence stays a token at a time); a full layer's
queries in blocks of `_Q_BLOCK` against all keys; the MoE in stretches of
`_T_BLOCK` tokens, one expert at a time; the head in blocks of columns. Every
matmul runs under `jax.default_matmul_precision("highest")`: on a TPU a
float32 matmul is bf16 passes otherwise.
"""

import functools

import jax
import jax.numpy as jnp

_Q_BLOCK = 64         # query positions a block: [H, 64, T] f32 scores
_T_BLOCK = 4096       # tokens a stretch of a linear layer or the MoE
_HEAD_BLOCK = 8192    # columns of the head cast to f32 at a time


def _f32(x):
    return x.astype(jnp.float32)


def _as(w, dtype):
    """The weight as it is, or rounded to `dtype` (a lower precision's
    control) and back. Vectors (norm scales, A_log, dt_bias) stay."""
    if dtype is None or w.ndim < 2:
        return w
    return w.astype(dtype).astype(w.dtype)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(scale)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _kernel(layer, name):
    return _f32(layer[name]["kernel"])


@functools.partial(jax.jit, static_argnames=("static",))
def _linear_stretch(x, kda, norm_scale, state, carried, static):
    """x [T, D] (a stretch of one sequence) -> (x + KDA(norm(x)), state',
    the convolution's last inputs). state [H, dk, dv]; carried [W - 1, Ch]."""
    h_n, dk, dv, eps = static
    t = x.shape[0]
    h = _rms_norm(x, norm_scale, eps)
    mixed = jnp.concatenate([h @ _kernel(kda, "wq"), h @ _kernel(kda, "wk"),
                             h @ _kernel(kda, "wv")], -1)
    conv_w = _f32(kda["conv"])                                  # [W, Ch]
    w = conv_w.shape[0]
    window = jnp.concatenate([carried, mixed], 0)               # [W-1+T, Ch]
    c = jax.nn.silu(sum(window[j:j + t] * conv_w[j] for j in range(w)))
    q, k, v = jnp.split(c, [h_n * dk, 2 * h_n * dk], -1)
    q = _l2(q.reshape(t, h_n, dk)) / jnp.sqrt(jnp.float32(dk))
    k = _l2(k.reshape(t, h_n, dk))
    v = v.reshape(t, h_n, dv)
    rate = (h @ _kernel(kda, "wf_a")) @ _kernel(kda, "wf_b") + kda["dt_bias"]
    alpha = jnp.exp(-jnp.exp(kda["A_log"])[:, None]
                    * jax.nn.softplus(rate).reshape(t, h_n, dk))
    beta = 2.0 * jax.nn.sigmoid(h @ _kernel(kda, "wb"))         # [T, H]

    def token(s, args):
        q_t, k_t, v_t, a_t, b_t = args
        s = s * a_t[..., None]                                  # diag(alpha) S
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[..., None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    state, o = jax.lax.scan(token, state, (q, k, v, alpha, beta))
    o = _rms_norm(o, kda["o_norm"]["scale"], eps).reshape(t, h_n * dv)
    gate = jax.nn.sigmoid((h @ _kernel(kda, "wg_a")) @ _kernel(kda, "wg_b"))
    return x + (o * gate) @ _kernel(kda, "wo"), state, window[t:]


def _linear_block(x, layer, sizes, weights_as):
    kda = jax.tree.map(lambda w: _as(w, weights_as), layer["kda"])
    h_n, dk, dv = (sizes["linear_heads"], sizes["linear_key_dim"],
                   sizes["linear_value_dim"])
    static = (h_n, dk, dv, sizes["norm_eps"])
    state = jnp.zeros((h_n, dk, dv), jnp.float32)
    carried = jnp.zeros((kda["conv"].shape[0] - 1, kda["conv"].shape[1]),
                        jnp.float32)
    out = []
    for start in range(0, x.shape[0], _T_BLOCK):
        y, state, carried = _linear_stretch(
            x[start:start + _T_BLOCK], kda, layer["attn_norm"]["scale"],
            state, carried, static)
        out.append(y)
    return jnp.concatenate(out, 0)


@functools.partial(jax.jit, static_argnames=("static",))
def _full_block(x, attn, norm_scale, static):
    """x [T, D] -> x + gated softmax attention(norm(x)), no positions."""
    n_heads, n_kv, hd, eps = static
    t = x.shape[0]
    h = _rms_norm(x, norm_scale, eps)
    k = (h @ _kernel(attn, "wk")).reshape(t, n_kv, hd)
    v = (h @ _kernel(attn, "wv")).reshape(t, n_kv, hd)
    wq, wg = _kernel(attn, "wq"), _kernel(attn, "w_gate")
    pad = -t % _Q_BLOCK
    hp = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, _Q_BLOCK, h.shape[1])
    starts = jnp.arange(hp.shape[0]) * _Q_BLOCK

    def block(args):
        hb, start = args
        q = (hb @ wq).reshape(_Q_BLOCK, n_kv, n_heads // n_kv, hd)
        rows = start + jnp.arange(_Q_BLOCK)[:, None]
        causal = jnp.arange(t)[None, :] <= rows
        s = jnp.einsum("qkgd,skd->kgqs", q, k) / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), -1)
        o = jnp.einsum("kgqs,skd->qkgd", p, v).reshape(_Q_BLOCK, -1)
        return o * jax.nn.sigmoid(hb @ wg)

    out = jax.lax.map(block, (hp, starts))
    out = out.reshape(-1, out.shape[-1])[:t]
    return x + out @ _kernel(attn, "wo")


@functools.partial(jax.jit, static_argnames=("top_k", "first", "eps",
                                             "weights_as"))
def _moe_stretch(x, layer, top_k, first, eps, weights_as):
    """x + this chip's part of the routed sum + the shared expert."""
    moe = layer["moe"]
    h = _rms_norm(x, layer["mlp_norm"]["scale"], eps)
    scores = jax.nn.sigmoid(h @ _f32(_as(moe["router"]["kernel"], weights_as)))
    vals, idx = jax.lax.top_k(scores, top_k)
    vals = vals / jnp.sum(vals, -1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1]) * vals[..., None], 1)
    held = moe["w_gate"].shape[0]
    gates = jax.lax.dynamic_slice_in_dim(gates, first, held, axis=1)

    def swiglu(w_gate, w_up, w_down):
        w_gate, w_up, w_down = (_f32(_as(w, weights_as))
                                for w in (w_gate, w_up, w_down))
        return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down

    def expert(y, args):
        *w, gate = args
        return y + gate[:, None] * swiglu(*w), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (moe["w_gate"], moe["w_up"], moe["w_down"], gates.T))
    if "shared_gate" in moe:
        y = y + swiglu(*(moe[n]["kernel"] for n in
                         ("shared_gate", "shared_up", "shared_down")))
    return x + y


def moe_block(x, layer, sizes, weights_as=None):
    """The MoE of one layer over x [T, D] (residual included)."""
    return jnp.concatenate([
        _moe_stretch(x[s:s + _T_BLOCK], layer, sizes["top_k"],
                     sizes.get("experts_first", 0), sizes["norm_eps"],
                     weights_as)
        for s in range(0, x.shape[0], _T_BLOCK)], 0)


def hidden_states(params, tokens, sizes: dict, weights_as=None):
    """Final-norm hidden states [T, D] of one sequence of token ids [T].
    `weights_as`: every matrix rounded to that type first."""
    p = params["params"]
    tokens = jnp.asarray(tokens)
    with jax.default_matmul_precision("highest"):
        x = _f32(_as(p["embed"]["embedding"], weights_as)[tokens])
        for i in range(sizes["n_layers"]):
            layer = p[f"layers_{i}"]
            if i % sizes["full_attn_every"]:
                x = _linear_block(x, layer, sizes, weights_as)
            else:
                attn = jax.tree.map(lambda w: _as(w, weights_as),
                                    layer["attn"])
                x = _full_block(
                    x, attn, layer["attn_norm"]["scale"],
                    (sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"],
                     sizes["norm_eps"]))
            x = moe_block(x, layer, sizes, weights_as)
        return jax.jit(_rms_norm, static_argnums=2)(
            x, p["final_norm"]["scale"], sizes["norm_eps"])


@jax.jit
def _logits(hidden, w_head):
    return hidden @ _f32(w_head)


def logits_of(params, tokens, sizes: dict, n_last: int, weights_as=None):
    """The logits [n_last, V] that follow each of the last `n_last` tokens of
    `tokens` (one sequence), over the vocabulary slice the head holds."""
    hidden = hidden_states(params, jnp.asarray(tokens, jnp.int32), sizes,
                           weights_as)[-n_last:]
    w_head = params["params"]["lm_head"]["kernel"]
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate([
            _logits(hidden, _as(w_head[:, c:c + _HEAD_BLOCK], weights_as))
            for c in range(0, w_head.shape[1], _HEAD_BLOCK)], axis=-1)


def logprobs_of(params, tokens, sizes: dict, n_last: int, weights_as=None):
    """log p(tokens[i] | tokens[:i]) for the last `n_last` tokens of one
    sequence: what a server that was given tokens[:-n_last] as the prompt and
    generated the rest must report, teacher-forced on its own tokens."""
    tokens = jnp.asarray(tokens, jnp.int32)
    logits = logits_of(params, tokens[:-1], sizes, n_last, weights_as)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, tokens[-n_last:, None], axis=-1)[:, 0]
