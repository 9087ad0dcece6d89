"""Reference (XLA) attention, RoPE, and KV-cache decode attention.

These are the non-pallas paths: pure jnp/lax code that XLA fuses well on TPU
and that runs identically on the CPU test mesh. `flash_attention` (pallas) is
numerically checked against `mha_reference` in tests.

Reference contrast: the reference reaches attention through torch SDPA /
flash-attn CUDA kernels (rllib torch models; serve LLM replicas). Here the
reference path is einsum + f32 softmax, shaped for the MXU: [B, T, H, D]
activations, GQA via a grouped head axis, bf16 inputs with f32 accumulation.
"""

import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-but-finite: keeps masked softmax rows NaN-free


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_table(max_len: int, head_dim: int, theta: float = 10000.0):
    """Precompute (sin, cos) tables, each [max_len, head_dim // 2], f32."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = jnp.arange(max_len, dtype=jnp.float32)[:, None] * freqs[None, :]
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
               sections=None, interleaved: bool = False):
    """Rotate-half RoPE (`interleaved`: frequency i turns the pair
    (2i, 2i + 1) of a head, GPT-J's layout, and not (i, i + D/2): the form
    of the weights as given, for the uncached forward. The compiler folds its
    pair reshape through the projection into a relayout of the WEIGHT in
    every call, so a served model never takes it: `LLMServer` splits the
    pairs of wq's and wk's columns once at load, `models/llama.py
    split_rotary_pairs`, and its programs run the rotate-half form).
    x: [B, T, H, D], positions: [B, T] int32, or
    [3, B, T] with `sections` (s_t, s_h, s_w) summing to D/2: of the D/2
    frequencies the first s_t turn by positions[0] (temporal), the next s_h
    by positions[1] (height), the rest by positions[2] (width). Text has all
    three equal, which is [B, T] positions exactly.

    Computed in f32 and cast back to x.dtype (bf16 rotation loses precision
    at long context).
    """
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if positions.ndim == 3:
        assert sections is not None and sum(sections) == d // 2, sections
        component = jnp.repeat(jnp.arange(len(sections)),
                               jnp.asarray(sections),
                               total_repeat_length=d // 2)     # [D/2]
        per_freq = jnp.moveaxis(positions, 0, -1)[..., component]
        angles = per_freq.astype(jnp.float32) * freqs          # [B, T, D/2]
    else:
        angles = positions[..., None].astype(jnp.float32) * freqs
    sin = jnp.sin(angles)[:, :, None, :]  # [B, T, 1, D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    if interleaved:
        pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense (XLA) attention with GQA
# ---------------------------------------------------------------------------

def mha_reference(
    q: jax.Array,  # [B, Tq, H, D]
    k: jax.Array,  # [B, Tk, Kh, D] (GQA: H = Kh * groups)
    v: jax.Array,  # [B, Tk, Kh, D]
    causal: bool = True,
    mask: Optional[jax.Array] = None,  # [B, Tq, Tk] or broadcastable, True=keep
    q_offset: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Grouped-query attention, f32 softmax, returns [B, Tq, H, D] in q.dtype.

    `q_offset` shifts query positions for causal masking (decode / chunked
    prefill: queries start at absolute position q_offset).
    """
    b, tq, h, d = q.shape
    kh = k.shape[2]
    assert h % kh == 0, f"{h} heads not divisible by {kh} kv heads"
    g = h // kh
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    qg = q.reshape(b, tq, kh, g, d)
    # [B, Kh, G, Tq, Tk]
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k, preferred_element_type=jnp.float32)
    s = s * scale

    if causal:
        tk = k.shape[1]
        rows = jnp.arange(tq)[:, None] + q_offset
        cols = jnp.arange(tk)[None, :]
        s = jnp.where(rows >= cols, s, NEG_INF)
    if mask is not None:
        s = jnp.where(mask[:, None, None, :, :] if mask.ndim == 3 else mask, s, NEG_INF)

    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", p.astype(v.dtype), v)
    return out.reshape(b, tq, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention over a (pre-allocated) KV cache
# ---------------------------------------------------------------------------

def decode_attention(
    q: jax.Array,        # [B, T, H, D] — new-token queries (T=1 decode, T>1 chunked prefill)
    k_cache: jax.Array,  # [B, Smax, Kh, D] — cache with the new K already written
    v_cache: jax.Array,  # [B, Smax, Kh, D]
    lengths: jax.Array,  # [B] int32 — tokens in cache BEFORE this chunk
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Decode/chunked-prefill attention against a static-shape cache.

    Query j sits at absolute position lengths+j and attends cache slots
    ≤ that position (with `window`, the last `window` of them, its own
    included). The whole cache is read and invalid slots masked — on
    TPU a masked dense read of a static cache beats dynamic-shape gathers,
    which would force recompilation per step.
    """
    b, t, h, d = q.shape
    smax, kh = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    qg = q.reshape(b, t, kh, g, d)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, k_cache, preferred_element_type=jnp.float32)
    s = s * scale
    pos = lengths[:, None, None] + jnp.arange(t)[None, :, None]    # [B, T, 1]
    valid = jnp.arange(smax)[None, None, :] <= pos                 # [B, T, Smax]
    if window is not None:
        valid &= jnp.arange(smax)[None, None, :] > pos - window
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(b, t, h, d).astype(q.dtype)


def blockwise_prefill_attention(
    q: jax.Array,        # [B, T, H, D]: a prefill chunk's queries
    k_cache: jax.Array,  # [B, Smax, Kh, D]: the row's keys, the chunk's written
    v_cache: jax.Array,  # [B, Smax, Kh, D]
    lengths: jax.Array,  # [B] int32: tokens in the row BEFORE this chunk
    scale: Optional[float] = None,
    key_block: int = 512,
    window: Optional[int] = None,
) -> jax.Array:
    """`decode_attention` for rows too long to score at once: the same
    absolute-position causal mask, by key blocks under an online softmax, as
    far as the last query reaches (a loop with a dynamic bound, so a short
    row in a long table pays for its own keys only; with `window` it starts
    at the block of the first key the first query sees). Nothing of size
    [T, heads, Smax] is held."""
    b, t, h, d = q.shape
    smax, kh = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    kb = min(key_block, smax)
    if smax % kb:
        pad = kb - smax % kb
        k_cache, v_cache = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                            for x in (k_cache, v_cache))
    qg = q.reshape(b, t, kh, g, d)
    pos = lengths[:, None] + jnp.arange(t)[None]                  # [B, T]
    n_blocks = (jnp.max(pos) + kb) // kb
    col = jnp.arange(kb)

    def block(i, carry):
        m, l, acc = carry
        k = jax.lax.dynamic_slice_in_dim(k_cache, i * kb, kb, 1)
        v = jax.lax.dynamic_slice_in_dim(v_cache, i * kb, kb, 1)
        s = jnp.einsum("btkgd,bskd->bkgts", qg, k,
                       preferred_element_type=jnp.float32) * scale
        keep = (i * kb + col)[None, None] <= pos[:, :, None]
        if window is not None:
            keep &= (i * kb + col)[None, None] > pos[:, :, None] - window
        keep = keep[:, None, None]
        s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.where(keep, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "bkgts,bskd->bkgtd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((b, kh, g, t), NEG_INF, jnp.float32),
            jnp.zeros((b, kh, g, t), jnp.float32),
            jnp.zeros((b, kh, g, t, d), jnp.float32))
    first = (0 if window is None
             else jnp.maximum(jnp.min(pos) - window + 1, 0) // kb)
    _, l, acc = jax.lax.fori_loop(first, n_blocks, block, init)
    out = acc / jnp.maximum(l, 1e-30)[..., None]                  # [B,Kh,G,T,D]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, h, d).astype(q.dtype)
