"""The plain reference for Command A+ (command-a-plus-05-2026, `cohere2_moe`)
as one chip's share: the forward pass in straightforward `jax.numpy` and
float32, written from the layer's equations (the configuration's file lists
what the published config does not fix, under `assumed`). A full pass over
the whole sequence with explicit masks: no cache, no pages, no kernels.

Layer l with input x [T, D] (a PARALLEL block: attention and the experts both
read one norm of x and are summed into the residual):

1. n = (x - mean(x)) / sqrt(var(x) + eps) * w over the hidden axis, no bias.
2. q = n Wq [T, H, hd], k = n Wk, v = n Wv [T, Kh, hd]; no bias, no q/k norm.
3. l % 4 != 3, a SLIDING layer: q and k turned by rotary over all hd
   dimensions in INTERLEAVED pairs (2i, 2i + 1), angle t * theta^(-2i / hd);
   query t sees key s iff t - window < s <= t. l % 4 == 3, a FULL layer: no
   positions at all; query t sees key s iff s <= t.
4. a[t, h] = sum_s softmax_s(q[t, h] . k[s, kv(h)] / sqrt(hd)) v[s, kv(h)],
   attn = concat(a) Wo.
5. On the same n: s = sigmoid(n Wr) over ALL `n_experts`, the `top_k`
   largest, gates s_i / sum of the chosen s. THE SHARE: the bank holds the
   experts `experts_first` .. `experts_first + held - 1` (held = the leading
   dimension of the bank's weights: 16 of 128 in the benchmark's cut, all of
   them in an uncut layer); a chosen expert that is held is applied (SwiGLU)
   under its gate, the others are another chip's and add nothing here.
   shared = (1 / n_shared) sum_j S_j(n), each S_j a SwiGLU of the experts'
   width whose weights are the j-th slice of the shared bank's columns (gate,
   up) and rows (down).
6. y = x + attn + routed + shared.

Then the final norm of the same form and logits = h E^T * logit_scale over the
tied embedding's rows (the vocabulary slice the file holds). It reads the
program's parameter tree and imports nothing of the program.

Departures from the description, each for memory only (it runs beside 13 GB
of weights and cache): one layer at a time; a layer's queries in blocks of
`_Q_BLOCK` against all keys under the mask, each block through Wo at once
(the heads' outputs of 20k tokens would be 1.3 GB), a sliding layer's block
against the stretch of keys that holds its queries' windows and not against
all keys (the mask is the same explicit one); the experts in stretches of
`_T_BLOCK` tokens, one expert at a time; the head in blocks of rows. Every
matmul runs under `jax.default_matmul_precision("highest")`: on a TPU a
float32 matmul is bf16 passes otherwise.
"""

import functools

import jax
import jax.numpy as jnp

_Q_BLOCK = 16         # query positions a block: [H, 16, T] f32 scores
_T_BLOCK = 2048       # tokens a stretch of the experts
_HEAD_BLOCK = 8192    # rows of the embedding cast to f32 at a time


def _f32(x):
    return x.astype(jnp.float32)


def _as(w, dtype):
    """The weight as it is, or rounded to `dtype` (a lower precision's
    control) and back. Vectors (norm scales) stay."""
    if dtype is None or w.ndim < 2:
        return w
    return w.astype(dtype).astype(w.dtype)


def layer_norm(x, scale, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(scale)


def rotary_interleaved(x, positions, theta):
    """x [T, heads, hd]: pair (2i, 2i + 1) of every head turned by the angle
    positions[t] * theta^(-2i / hd)."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = positions[:, None].astype(jnp.float32) * freqs          # [T, hd/2]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape)


def _kernel(layer, name):
    return _f32(layer[name]["kernel"])


@functools.partial(jax.jit, static_argnames=("static",))
def _attention(n, attn, static):
    """n [T, D], the layer's normed input -> attention's output [T, D]."""
    n_heads, n_kv, hd, window, theta = static
    t = n.shape[0]
    k = (n @ _kernel(attn, "wk")).reshape(t, n_kv, hd)
    v = (n @ _kernel(attn, "wv")).reshape(t, n_kv, hd)
    if window:
        k = rotary_interleaved(k, jnp.arange(t), theta)
    wq, wo = _kernel(attn, "wq"), _kernel(attn, "wo")
    pad = -t % _Q_BLOCK
    blocks = jnp.pad(n, ((0, pad), (0, 0))).reshape(-1, _Q_BLOCK, n.shape[1])
    starts = jnp.arange(blocks.shape[0]) * _Q_BLOCK
    # a sliding layer's block of queries is scored against the stretch of
    # keys that holds every window of the block (positions start - window + 1
    # on, `span` of them), under the same explicit mask; a full layer's
    # against all keys
    span = window + _Q_BLOCK if window else t
    if window:
        k, v = (jnp.pad(x, ((window, _Q_BLOCK + 1), (0, 0), (0, 0)))
                for x in (k, v))

    def block(args):
        nb, start = args
        rows = start + jnp.arange(_Q_BLOCK)
        q = (nb @ wq).reshape(_Q_BLOCK, n_heads, hd)
        if window:
            q = rotary_interleaved(q, rows, theta)
        q = q.reshape(_Q_BLOCK, n_kv, n_heads // n_kv, hd)
        first = start + 1 - window if window else 0     # may lie before 0
        cols = first + jnp.arange(span)[None, :]
        seen = (cols <= rows[:, None]) & (cols >= 0) & (cols < t)
        kb, vb = k, v
        if window:
            seen &= cols > rows[:, None] - window
            kb, vb = (jax.lax.dynamic_slice_in_dim(x, first + window, span)
                      for x in (k, v))
        s = jnp.einsum("qkgd,skd->kgqs", q, kb) / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        return jnp.einsum("kgqs,skd->qkgd", p, vb).reshape(_Q_BLOCK, -1) @ wo

    out = jax.lax.map(block, (blocks, starts))
    return out.reshape(-1, out.shape[-1])[:t]


@functools.partial(jax.jit, static_argnames=("top_k", "first", "n_shared",
                                             "weights_as"))
def _experts(n, moe, top_k, first, n_shared, weights_as):
    """n [T, D] -> this chip's part of the routed sum + the shared experts'
    mean."""
    scores = jax.nn.sigmoid(n @ _f32(_as(moe["router"]["kernel"], weights_as)))
    vals, idx = jax.lax.top_k(scores, top_k)
    vals = vals / jnp.sum(vals, -1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1]) * vals[..., None], 1)
    held = moe["w_gate"].shape[0]
    gates = jax.lax.dynamic_slice_in_dim(gates, first, held, axis=1)

    def swiglu(w_gate, w_up, w_down):
        w_gate, w_up, w_down = (_f32(_as(w, weights_as))
                                for w in (w_gate, w_up, w_down))
        return (jax.nn.silu(n @ w_gate) * (n @ w_up)) @ w_down

    def expert(y, args):
        *w, gate = args
        return y + gate[:, None] * swiglu(*w), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(n),
                        (moe["w_gate"], moe["w_up"], moe["w_down"], gates.T))
    if n_shared:
        s_gate, s_up, s_down = (moe[k]["kernel"] for k in
                                ("shared_gate", "shared_up", "shared_down"))
        width = s_down.shape[0] // n_shared
        for j in range(n_shared):
            cut = slice(j * width, (j + 1) * width)
            y = y + swiglu(s_gate[:, cut], s_up[:, cut], s_down[cut]) / n_shared
    return y


def experts_of(n, layer, sizes, weights_as=None):
    """The experts' part of one layer for its normed input n [T, D]."""
    return jnp.concatenate([
        _experts(n[s:s + _T_BLOCK], layer["moe"], sizes["top_k"],
                 sizes.get("experts_first", 0), sizes["n_shared"], weights_as)
        for s in range(0, n.shape[0], _T_BLOCK)], 0)


def layer_of(x, layer, kind, sizes, weights_as=None):
    """One parallel block over x [T, D]: x + attn + routed + shared."""
    with jax.default_matmul_precision("highest"):
        n = jax.jit(layer_norm, static_argnums=2)(
            x, layer["attn_norm"]["scale"], sizes["norm_eps"])
        attn = jax.tree.map(lambda w: _as(w, weights_as), layer["attn"])
        static = (sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"],
                  sizes["window"] if kind == "sliding" else 0,
                  float(sizes["rope_theta"]))
        return x + _attention(n, attn, static) + experts_of(
            n, layer, sizes, weights_as)


def hidden_states(params, tokens, sizes: dict, weights_as=None):
    """Final-norm hidden states [T, D] of one sequence of token ids [T].
    `weights_as`: every matrix rounded to that type first."""
    p = params["params"]
    tokens = jnp.asarray(tokens)
    kinds = sizes["layer_types"]
    x = _f32(_as(p["embed"]["embedding"], weights_as)[tokens])
    for i in range(sizes["n_layers"]):
        x = layer_of(x, p[f"layers_{i}"], kinds[i % len(kinds)], sizes,
                     weights_as)
    return jax.jit(layer_norm, static_argnums=2)(
        x, p["final_norm"]["scale"], sizes["norm_eps"])


@jax.jit
def _logits(hidden, rows):
    return hidden @ _f32(rows).T


def logits_of(params, tokens, sizes: dict, n_last: int, weights_as=None):
    """The logits [n_last, V] that follow each of the last `n_last` tokens of
    `tokens` (one sequence), over the vocabulary slice the embedding holds."""
    hidden = hidden_states(params, jnp.asarray(tokens, jnp.int32), sizes,
                           weights_as)[-n_last:]
    embed = params["params"]["embed"]["embedding"]
    with jax.default_matmul_precision("highest"):
        logits = jnp.concatenate([
            _logits(hidden, _as(embed[r:r + _HEAD_BLOCK], weights_as))
            for r in range(0, embed.shape[0], _HEAD_BLOCK)], axis=-1)
    return logits * sizes.get("logit_scale", 1.0)


def logprobs_of(params, tokens, sizes: dict, n_last: int, weights_as=None):
    """log p(tokens[i] | tokens[:i]) for the last `n_last` tokens of one
    sequence: what a server that was given tokens[:-n_last] as the prompt and
    generated the rest must report, teacher-forced on its own tokens."""
    tokens = jnp.asarray(tokens, jnp.int32)
    logits = logits_of(params, tokens[:-1], sizes, n_last, weights_as)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, tokens[-n_last:, None], axis=-1)[:, 0]
