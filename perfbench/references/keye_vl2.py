"""The plain reference for Keye-VL-2.0-30B-A3B's language model: the forward
pass in straightforward `jax.numpy` and float32, written from the layer's
equations (the configuration's file lists what the published config does not
fix, under `assumed`):

1. h = RMSNorm(x) (learned scale).
2. q = h Wq [T, H, D], k = h Wk, v = h Wv [T, Kh, D]; q and k pass an RMS norm
   over each head's D values (learned scale of D).
3. Rotate-half rotary on q and k. Of the D/2 frequencies the first s_t turn by
   the temporal position, the next s_h by the height, the rest by the width
   (`rope_sections`); text has all three equal.
4. Indexer: qI = h WqI [T, J, Di], kI = LayerNorm(h WkI) [T, Di] (one key
   head), w = h Ww [T, J]; rotary by the temporal position on qI and kI;
   I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]) for s <= t. S_t is the set of
   the `index_topk` positions s <= t of highest I[t, s] (ties to the earlier
   position, as `lax.top_k` breaks them), all of them while t + 1 <= index_topk.
5. o[t, a] = sum_{s in S_t} softmax_s(q[t, a] . k[s, kv(a)] / sqrt(D)) v[s, kv(a)];
   x = x + concat(o) Wo.
6. h2 = RMSNorm(x); p = softmax(h2 Wr) over all experts in f32, the top_k
   largest, gates renormalised to sum to one; every chosen expert applied
   (SwiGLU, no token dropped, no shared expert); x = x + sum_e g_e expert_e(h2).

Then a final RMSNorm and an untied head. No kernels, no cache, no batching. It
reads the program's parameter tree (`params/layers_N/attn/wq/kernel`,
`attn/q_norm/scale`, `attn/indexer/{wq,wk,w}/kernel`, `attn/indexer/k_norm/
{scale,bias}`, `moe/{router/kernel,w_gate,w_up,w_down}` ...) and imports
nothing of the program.

Departures, each for memory only (it runs beside 13 GB of weights and cache):
one layer at a time, K, V and kI of the whole sequence first and then the
queries in blocks of `_Q_BLOCK` (the selection is a mask over a block's score
rows, never a [T, T] array), an expert layer one expert at a time over every
token with its gate (0 where the token did not choose it), and the head in
blocks of columns. Every matmul runs under
`jax.default_matmul_precision("highest")`: on a TPU a float32 matmul is bf16
passes otherwise.
"""

import functools

import jax
import jax.numpy as jnp

_Q_BLOCK = 128        # query positions a block: [H, 128, T] f32 scores
_HEAD_BLOCK = 16384   # columns of the head cast to f32 at a time


def _f32(x):
    return x.astype(jnp.float32)


def _as(w, dtype):
    """The weight as it is, or rounded to `dtype` (a lower precision's
    control) and back."""
    return w if dtype is None else w.astype(dtype).astype(w.dtype)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(scale)


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(scale) + _f32(bias)


def _rope(x, positions, theta, sections=None):
    """x [T, H, D]; positions [T], or [3, T] with `sections`: frequency i
    turns by the component its section names. Rotate-half."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if positions.ndim == 2:
        component = jnp.concatenate([jnp.full((n,), c) for c, n in
                                     enumerate(sections)])
        positions = positions[component, :].T                # [T, D/2]
    else:
        positions = positions[:, None]
    ang = positions.astype(jnp.float32) * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _selected(scores, topk):
    """[Q, T] scores, -inf where not causal -> bool [Q, T]: each row's `topk`
    highest (every causal key of a row that has no more)."""
    t = scores.shape[-1]
    if t <= topk:
        return scores > -jnp.inf
    idx = jax.lax.top_k(scores, topk)[1]
    rows = jnp.arange(scores.shape[0])[:, None]
    picked = jnp.zeros(scores.shape, bool).at[rows, idx].set(True)
    return picked & (scores > -jnp.inf)


@functools.partial(jax.jit, static_argnames=("static",))
def _attn_block(x, layer, positions, static):
    """x [T, D] -> x + sparse attention(norm(x)) for one sequence.
    positions [3, T]."""
    n_heads, n_kv, hd, theta, eps, sections, j, di, topk = static
    a, ix = layer["attn"], layer["attn"]["indexer"]
    t = x.shape[0]
    h = _rms_norm(x, layer["attn_norm"]["scale"], eps)
    temporal = positions[0]
    k = _rms_norm((h @ _f32(a["wk"]["kernel"])).reshape(t, n_kv, hd),
                  a["k_norm"]["scale"], eps)
    k = _rope(k, positions, theta, sections)
    v = (h @ _f32(a["wv"]["kernel"])).reshape(t, n_kv, hd)
    ki = _layer_norm(h @ _f32(ix["wk"]["kernel"]), ix["k_norm"]["scale"],
                     ix["k_norm"]["bias"], eps)
    ki = _rope(ki[:, None, :], temporal, theta)[:, 0]          # [T, Di]
    wq, wqi, ww = (_f32(a["wq"]["kernel"]), _f32(ix["wq"]["kernel"]),
                   _f32(ix["w"]["kernel"]))
    pad = -t % _Q_BLOCK
    hp = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, _Q_BLOCK, h.shape[1])
    pp = jnp.pad(positions, ((0, 0), (0, pad))).reshape(3, -1, _Q_BLOCK)
    starts = jnp.arange(hp.shape[0]) * _Q_BLOCK

    def block(args):
        hb, pb, start = args                                   # [Q, D], [3, Q]
        q = _rms_norm((hb @ wq).reshape(_Q_BLOCK, n_heads, hd),
                      a["q_norm"]["scale"], eps)
        q = _rope(q, pb, theta, sections).reshape(_Q_BLOCK, n_kv,
                                                  n_heads // n_kv, hd)
        qi = _rope((hb @ wqi).reshape(_Q_BLOCK, j, di), pb[0], theta)
        w = hb @ ww                                            # [Q, J]
        rows = start + jnp.arange(_Q_BLOCK)[:, None]
        causal = jnp.arange(t)[None, :] <= rows
        index = jnp.einsum("qjs,qj->qs", jax.nn.relu(
            jnp.einsum("qjd,sd->qjs", qi, ki)), w)
        keep = _selected(jnp.where(causal, index, -jnp.inf), topk)
        s = jnp.einsum("qkgd,skd->kgqs", q, k) / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(keep[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v).reshape(_Q_BLOCK, -1)

    out = jax.lax.map(block, (hp, pp.transpose(1, 0, 2), starts))
    out = out.reshape(-1, out.shape[-1])[:t]
    return x + out @ _f32(a["wo"]["kernel"])


@functools.partial(jax.jit, static_argnames=("top_k", "eps", "weights_as"))
def _moe_block(x, layer, top_k, eps, weights_as):
    """x + sum_e gate_e expert_e(norm(x)): one expert at a time over every
    token, weighted by its gate (0 for the experts a token did not choose)."""
    moe = layer["moe"]
    h = _rms_norm(x, layer["mlp_norm"]["scale"], eps)
    probs = jax.nn.softmax(h @ _f32(_as(moe["router"]["kernel"], weights_as)),
                           axis=-1)
    vals, idx = jax.lax.top_k(probs, top_k)
    vals = vals / jnp.sum(vals, -1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1]) * vals[..., None], 1)

    def expert(y, args):
        w_gate, w_up, w_down, gate = args
        w_gate, w_up, w_down = (_f32(_as(w, weights_as))
                                for w in (w_gate, w_up, w_down))
        out = (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down
        return y + gate[:, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (moe["w_gate"], moe["w_up"], moe["w_down"], gates.T))
    return x + y


def hidden_states(params, tokens, sizes: dict, weights_as=None,
                  positions=None):
    """Final-norm hidden states [T, D] of one sequence of token ids [T].
    `positions` [3, T] (temporal, height, width); none given: text, all three
    the token's index. `weights_as`: every matrix rounded to that type first."""
    p = params["params"]
    tokens = jnp.asarray(tokens)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[0])[None],
                                     (3, tokens.shape[0]))
    static = (sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"],
              sizes["rope_theta"], sizes["norm_eps"],
              tuple(sizes["rope_sections"]), sizes["index_heads"],
              sizes["index_dim"], sizes["index_topk"])
    with jax.default_matmul_precision("highest"):
        x = _f32(_as(p["embed"]["embedding"][tokens], weights_as))
        for i in range(sizes["n_layers"]):
            layer = p[f"layers_{i}"]
            # norm scales and the LayerNorm's bias are not matrices
            attn = jax.tree.map(
                lambda w: _as(w, weights_as) if w.ndim > 1 else w,
                layer["attn"])
            x = _attn_block(x, {**layer, "attn": attn}, positions, static)
            x = _moe_block(x, layer, sizes["top_k"], sizes["norm_eps"],
                           weights_as)
        return jax.jit(_rms_norm, static_argnums=2)(
            x, p["final_norm"]["scale"], sizes["norm_eps"])


@jax.jit
def _logits(hidden, w_head):
    return hidden @ _f32(w_head)


def logprobs_of(params, tokens, sizes: dict, n_last: int, weights_as=None):
    """log p(tokens[i] | tokens[:i]) for the last `n_last` tokens of one
    sequence: what a server that was given tokens[:-n_last] as the prompt and
    generated the rest must report, teacher-forced on its own tokens."""
    tokens = jnp.asarray(tokens, jnp.int32)
    hidden = hidden_states(params, tokens[:-1], sizes, weights_as)[-n_last:]
    w_head = params["params"]["lm_head"]["kernel"]
    with jax.default_matmul_precision("highest"):
        logits = jnp.concatenate([
            _logits(hidden, _as(w_head[:, c:c + _HEAD_BLOCK], weights_as))
            for c in range(0, w_head.shape[1], _HEAD_BLOCK)], axis=-1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, tokens[-n_last:, None], axis=-1)[:, 0]
