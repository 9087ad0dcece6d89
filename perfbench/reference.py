"""The plain reference for Llama-family decoders (Mistral, Mixtral): the
forward pass in straightforward `jax.numpy` and float32, written from the
published equations (pre-norm decoder; RMSNorm; rotate-half RoPE; grouped-query
causal attention; SwiGLU; for Mixtral a softmax router over all experts, top-2,
gates renormalised, every chosen expert applied: dropless). No kernels, no
cache, no batching tricks. It reads the program's parameter tree
(`params/layers_N/attn/wq/kernel` ...) and nothing else of the program.

Departures, each for memory only: one layer at a time (a jitted function per
layer shape), attention in blocks of query positions, the loss in blocks of
rows, and a Mixtral layer one expert at a time. Every matmul runs under
`jax.default_matmul_precision("highest")`: on a TPU a float32 matmul is bf16
passes otherwise.

This is the benchmark's copy and its judge of `correct`; later PRs cannot
change it.
"""

import functools

import jax
import jax.numpy as jnp

_Q_BLOCK = 512      # query positions per attention block
_ROW_BLOCK = 2048   # rows per block of the loss


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(scale)


def _rope(x, positions, theta):
    """x [T, H, D], positions [T]. Rotate-half, as the published models."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal grouped-query attention of one sequence. q [T, H, D], k/v
    [T, Kh, D]; query blocks keep the score matrix small."""
    t, h, d = q.shape
    kh = k.shape[1]
    q = q.reshape(t, kh, h // kh, d)
    outs = []
    for start in range(0, t, _Q_BLOCK):
        qb = q[start:start + _Q_BLOCK]
        s = jnp.einsum("qkgd,tkd->kgqt", qb, k) / jnp.sqrt(jnp.float32(d))
        rows = start + jnp.arange(qb.shape[0])[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= rows, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("kgqt,tkd->qkgd", p, v))
    return jnp.concatenate(outs, 0).reshape(t, h * d)


@functools.partial(jax.jit, static_argnames=("sizes",))
def _attn_block(x, layer, sizes):
    """x [T, D] -> x + attention(norm(x)) for one sequence."""
    n_heads, n_kv, hd, theta, eps = sizes
    a = layer["attn"]
    t = x.shape[0]
    h = _rms_norm(x, layer["attn_norm"]["scale"], eps)
    pos = jnp.arange(t)
    q = _rope((h @ _f32(a["wq"]["kernel"])).reshape(t, n_heads, hd), pos, theta)
    k = _rope((h @ _f32(a["wk"]["kernel"])).reshape(t, n_kv, hd), pos, theta)
    v = (h @ _f32(a["wv"]["kernel"])).reshape(t, n_kv, hd)
    return x + _attention(q, k, v) @ _f32(a["wo"]["kernel"])


@jax.jit
def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _f32(w_gate)) * (h @ _f32(w_up))) @ _f32(w_down)


@functools.partial(jax.jit, static_argnames=("top_k",))
def _route(h, router, top_k):
    """Gate weight of every expert for every token [T, E]: softmax over all
    experts, keep the top-k, renormalise; zero elsewhere."""
    probs = jax.nn.softmax(h @ _f32(router), axis=-1)
    vals, idx = jax.lax.top_k(probs, top_k)
    vals = vals / jnp.sum(vals, -1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(idx, probs.shape[-1]) * vals[..., None], 1)


def _as(w, dtype):
    """The weight as it is, or rounded to `dtype` (a lower precision's
    control) and back: one matrix at a time, a whole layer would not fit."""
    return w if dtype is None else w.astype(dtype).astype(w.dtype)


def _ffn_block(x, layer, eps, top_k, weights_as=None):
    h = jax.jit(_rms_norm, static_argnums=2)(x, layer["mlp_norm"]["scale"], eps)
    if "moe" not in layer:
        m = layer["mlp"]
        return x + _swiglu(h, *(_as(m[k]["kernel"], weights_as)
                                for k in ("w_gate", "w_up", "w_down")))
    moe = layer["moe"]
    gates = _route(h, _as(moe["router"]["kernel"], weights_as), top_k)
    for e in range(moe["w_gate"].shape[0]):   # every token through every
        # expert, weighted by its gate (0 for the experts it did not choose)
        x = x + gates[:, e:e + 1] * _swiglu(
            h, *(_as(moe[k][e], weights_as) for k in ("w_gate", "w_up", "w_down")))
    return x


def hidden_states(params, tokens, sizes: dict, weights_as=None):
    """Final-norm hidden states [T, D] of one sequence of token ids [T].
    `weights_as`: every matrix rounded to that type first."""
    p = params["params"]
    static = (sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"],
              sizes["rope_theta"], sizes["norm_eps"])
    with jax.default_matmul_precision("highest"):
        x = _f32(_as(p["embed"]["embedding"][jnp.asarray(tokens)], weights_as))
        for i in range(sizes["n_layers"]):
            layer = p[f"layers_{i}"]
            attn = jax.tree.map(lambda w: _as(w, weights_as), layer["attn"])
            x = _attn_block(x, {**layer, "attn": attn}, static)
            x = _ffn_block(x, layer, sizes["norm_eps"], sizes["top_k"],
                           weights_as)
        return jax.jit(_rms_norm, static_argnums=2)(
            x, p["final_norm"]["scale"], sizes["norm_eps"])


@jax.jit
def _token_logprobs(hidden, w_head, targets):
    logp = jax.nn.log_softmax(hidden @ _f32(w_head), axis=-1)
    return jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]


def logprobs_of(params, tokens, sizes: dict, n_last: int, weights_as=None):
    """log p(tokens[i] | tokens[:i]) for the last `n_last` tokens of one
    sequence: what a server that was given tokens[:-n_last] as the prompt and
    generated the rest must report, teacher-forced on its own tokens."""
    tokens = jnp.asarray(tokens, jnp.int32)
    hidden = hidden_states(params, tokens[:-1], sizes, weights_as)
    with jax.default_matmul_precision("highest"):
        return _token_logprobs(
            hidden[-n_last:],
            _as(params["params"]["lm_head"]["kernel"], weights_as),
            tokens[-n_last:])


def loss_of(params, batch, sizes: dict):
    """Mean next-token cross entropy of a batch [B, T+1] of token ids."""
    total, count = jnp.float32(0), 0
    w_head = params["params"]["lm_head"]["kernel"]
    for row in jnp.asarray(batch, jnp.int32):
        hidden = hidden_states(params, row[:-1], sizes)
        with jax.default_matmul_precision("highest"):
            for start in range(0, hidden.shape[0], _ROW_BLOCK):
                lp = _token_logprobs(hidden[start:start + _ROW_BLOCK], w_head,
                                     row[1 + start:1 + start + _ROW_BLOCK])
                total, count = total - jnp.sum(lp), count + lp.shape[0]
    return total / count
