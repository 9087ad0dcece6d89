"""Pallas flash attention (TPU): fwd + bwd kernels.

Forward: blocks of Q stream against blocks of K/V held in VMEM, online-softmax
accumulation in f32 scratch, causal blocks above the diagonal skipped entirely
(compute scales with the unmasked area).

Backward (FlashAttention-2 split, both pallas): a Q-centric pass accumulates
dQ over KV blocks, and a KV-centric pass accumulates dK/dV over Q blocks with
the GQA group folded into the grid so each KV head's gradients accumulate
across its G query heads in one scratch visit. P is recomputed from the saved
logsumexp; `delta = rowsum(dO·O)` is precomputed in XLA (one cheap
bandwidth-bound pass). Causal block-skipping applies in both passes.

Reference contrast: the reference gets this from flash-attn CUDA via torch.
On the CPU test mesh the same kernels run in pallas interpret mode, so
numerics are tested without hardware (SURVEY.md §4 models/ops).

Block sizes default to 1024 (per-grid-step overhead dominates small blocks);
fwd, dq and dkv all compile at 1024x1024 on a v5e under libtpu 0.0.34 and
match `mha_reference` at B=1,T=2048,H=32,Kh=8,D=64 (chip_smoke.py checks this
on every run). Their speed is not measured.

Serving has a forward kernel of its own at the end of the file,
`flash_continuation`: a prefill chunk that starts past position 0 against
its row's cached keys, with the start and the key bound values of the call.
"""

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128  # TPU lane width: row-stat scratch is kept lane-replicated


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_kv, num_kv_blocks):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: blocks strictly above the diagonal contribute nothing.
    run = (ik * block_kv < (iq + 1) * block_q) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
            cols = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(rows >= cols, s, -jnp.inf)

        m_prev = m_scr[:, :1]                                   # [bq, 1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)               # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        # exp(-inf - -inf) would be NaN on fully-masked rows; they can't occur
        # under the causal block skip (every kept block has a live diagonal).
        p = jnp.exp(s - m_new)                                  # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)                         # [bq, 1]
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log(l)  # [bq, 1]


def _flash_fwd(q, k, v, *, causal, scale, block_q, block_kv, interpret):
    """q: [B, H, T, D]; k, v: [B, Kh, S, D]. Returns (out, lse)."""
    b, h, tq, d = q.shape
    kh, tk = k.shape[1], k.shape[2]
    g = h // kh
    block_q = min(block_q, tq)
    block_kv = min(block_kv, tk)
    nq, nk = tq // block_q, tk // block_kv

    grid = (b, h, nq, nk)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_kv=block_kv, num_kv_blocks=nk)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda b_, h_, iq, ik, g=g: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, d), lambda b_, h_, iq, ik, g=g: (b_, h_ // g, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            # lse rides in [B, H, T, 1]: TPU lowering wants the trailing block
            # dims (bq, 1) aligned, which a rank-3 (1, 1, bq) block is not
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, tq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse[..., 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_kv, interpret):
    out, _ = _flash_fwd(q, k, v, causal=causal, scale=scale,
                        block_q=block_q, block_kv=block_kv, interpret=interpret)
    return out


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_kv, interpret):
    out, lse = _flash_fwd(q, k, v, causal=causal, scale=scale,
                          block_q=block_q, block_kv=block_kv, interpret=interpret)
    return out, (q, k, v, out, lse)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale, causal, block_q, block_kv, num_kv_blocks):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = (ik * block_kv < (iq + 1) * block_q) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]      # [bq, 1] f32
        delta = delta_ref[0, 0]  # [bq, 1] f32
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
            cols = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(rows >= cols, s, -jnp.inf)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, block_q, block_kv, num_q_blocks, group):
    ik = pl.program_id(2)
    ig = pl.program_id(3)
    iq = pl.program_id(4)

    @pl.when((ig == 0) & (iq == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # causal: q blocks strictly above the diagonal see none of this kv block
    run = ((iq + 1) * block_q > ik * block_kv) if causal else True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
            cols = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(rows >= cols, s, -jnp.inf)
        p = jnp.exp(s - lse)                       # [bq, bkv] f32
        pb = p.astype(q.dtype)
        # dv += P^T @ dO
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            pb, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        # dk += dS^T @ Q
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when((ig == group - 1) & (iq == num_q_blocks - 1))
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, do, *, causal, scale, block_q, block_kv,
               interpret):
    """q/do: [B, H, T, D]; k/v: [B, Kh, S, D]; lse: [B, H, T]."""
    b, h, tq, d = q.shape
    kh, tk = k.shape[1], k.shape[2]
    g = h // kh
    bq = min(block_q, tq)
    bkv = min(block_kv, tk)
    nq, nk = tq // bq, tk // bkv

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)          # [B, H, T, 1]
    lse4 = lse[..., None]                            # [B, H, T, 1]

    q_spec = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0))
    kv_spec = pl.BlockSpec((1, 1, bkv, d), lambda b_, h_, iq, ik, g=g: (b_, h_ // g, ik, 0))
    stat_spec = pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, iq, ik: (b_, h_, iq, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_kv=bkv, num_kv_blocks=nk),
        grid=(b, h, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse4, delta)

    # KV-centric pass: grid folds the GQA group so dk/dv scratch accumulates
    # across the G query heads sharing each KV head
    q_gspec = pl.BlockSpec((1, 1, bq, d),
                           lambda b_, kh_, ik, ig, iq, g=g: (b_, kh_ * g + ig, iq, 0))
    kv_gspec = pl.BlockSpec((1, 1, bkv, d), lambda b_, kh_, ik, ig, iq: (b_, kh_, ik, 0))
    stat_gspec = pl.BlockSpec((1, 1, bq, 1),
                              lambda b_, kh_, ik, ig, iq, g=g: (b_, kh_ * g + ig, iq, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_kv=bkv, num_q_blocks=nq, group=g),
        grid=(b, kh, nk, g, nq),
        in_specs=[q_gspec, kv_gspec, kv_gspec, q_gspec, stat_gspec, stat_gspec],
        out_specs=[kv_gspec, kv_gspec],
        out_shape=[jax.ShapeDtypeStruct((b, kh, tk, d), k.dtype),
                   jax.ShapeDtypeStruct((b, kh, tk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bkv, d), jnp.float32),
                        pltpu.VMEM((bkv, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse4, delta)
    return dq, dk, dv


def _flash_vjp_bwd(causal, scale, block_q, block_kv, interpret, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, do, causal=causal, scale=scale,
                            block_q=block_q, block_kv=block_kv,
                            interpret=interpret)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _shard_spec(q, k):
    """(PartitionSpec, mesh axes it uses) for running the kernel per shard of
    the context mesh (`jax.set_mesh`), or None with nothing to shard over.

    A pallas custom call has no partitioning rule: left bare under GSPMD,
    XLA all-gathers q/k/v and runs the WHOLE batch on every device. Attention
    is independent per batch row and per kv-head group, so batch splits over
    the data axes (`parallel.sharding.batch_spec`) and heads over `tp`; the
    sequence stays whole — splitting it is ring_attention's job."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return None
    from jax.sharding import AxisType, PartitionSpec

    from ray_tpu.parallel.sharding import batch_spec
    free = {a for a, t in zip(mesh.axis_names, mesh.axis_types)
            if t != AxisType.Manual and mesh.shape[a] > 1}

    def fit(axes, *dims):
        axes = tuple(a for a in axes if a in free)
        n = math.prod(mesh.shape[a] for a in axes)
        return axes if axes and all(d % n == 0 for d in dims) else None

    batch = fit(batch_spec()[0], q.shape[0])
    heads = fit(("tp",), q.shape[2], k.shape[2])
    if batch is None and heads is None:
        return None
    return PartitionSpec(batch, None, heads, None), {*(batch or ()),
                                                      *(heads or ())}


def flash_attention(
    q: jax.Array,  # [B, T, H, D]
    k: jax.Array,  # [B, S, Kh, D]
    v: jax.Array,  # [B, S, Kh, D]
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 1024,
    block_kv: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention in [B, T, H, D] layout (matches `mha_reference`).

    `interpret=None` selects by backend: pallas-compiled on TPU, interpret
    mode elsewhere. Sequence lengths must tile into the (clipped) block
    sizes — the grid would silently drop the remainder rows, and an O(T²)
    fallback hiding behind the kernel's name is worse than an error; callers
    with ragged lengths pad, or call `mha_reference` themselves. Under a
    `jax.set_mesh` context the kernel runs per shard (see `_shard_spec`).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    tq, tk = q.shape[1], k.shape[1]
    if tq % min(block_q, tq) or tk % min(block_kv, tk):
        raise ValueError(
            f"flash_attention: seq lengths (q={tq}, kv={tk}) don't tile into "
            f"blocks ({block_q}, {block_kv})")
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])

    def kernel(q, k, v):
        out = _flash(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                     jnp.swapaxes(v, 1, 2), causal, scale, block_q, block_kv,
                     interpret)   # [B, H, T, D]
        return jnp.swapaxes(out, 1, 2)

    sharded = _shard_spec(q, k)
    if sharded is not None:
        spec, axes = sharded
        kernel = jax.shard_map(kernel, in_specs=(spec, spec, spec),
                               out_specs=spec, axis_names=axes,
                               check_vma=False)
    return kernel(q, k, v)


# ---------------------------------------------------------------------------
# A prefill continuation chunk over its row's cached prefix (serving; forward
# only). The training kernels above are not its callers and share nothing
# with it.
# ---------------------------------------------------------------------------

# Rows of one step's score matrix: the G query heads of a kv head stacked on
# `block_q` queries each, so that a K/V block is read once a kv head and the
# MXU sees a tall operand; and its columns, the keys of a block. f32 scores
# and weights of [1024, 1024] are 4 MiB each, inside the default VMEM limit.
# On the v5e (PR 38) a chunk of 1024 x 64 heads of 128 over a prefix of 30k
# took 7.9 ms at 1024 x 1024 (66% of the bf16 peak), 7.6 at 2048 x 1024 (which
# needs the limit raised), 8.0 at 1024 x 2048, and 16.1 at 1024 x 512 and
# 24.7 at 1024 x 256: a step's fixed work (the accumulators rescaled, the
# row statistics read and written back lane-wide) wants a wide block to
# spread over.
_CONT_ROWS = 1024
_CONT_BLOCK_KV = 1024


def continuation_blocks(t: int, g: int, dtype) -> Optional[int]:
    """The query block `flash_continuation` takes a chunk of `t` queries of
    `g` heads a kv head with, or None for a chunk it cannot take: its blocks
    have to tile the chunk, in whole sublane tiles of the operands' type (a
    bucket clamped to what a row has left can be any length)."""
    tile = 8 * 4 // jnp.dtype(dtype).itemsize
    # the largest power of two of queries whose g heads stack to `_CONT_ROWS`
    # rows at most: `_CONT_ROWS // g` itself where g is a power of two; 128
    # queries, 640 rows, at 5 heads a kv head, where 204 would tile nothing
    block_q = min(t, max(tile, 1 << ((_CONT_ROWS // g).bit_length() - 1)))
    return None if t % block_q or block_q % tile else block_q


def _window_first_block(first, window: int, block_kv: int):
    """The key block that holds the first key a query at position `first`
    sees through a window of `window` keys, its own included."""
    return jnp.maximum(first - window + 1, 0) // block_kv


def _continuation_kernel(start_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, scale, block_q, block_kv,
                         window=None):
    """Step (b, kh, iq, ik): fold key block ik into the accumulators of query
    block iq, for the G query heads of kv head kh at once. q_ref, o_ref:
    [G, block_q, D]; k_ref, v_ref: [block_kv, D]; scratch: running maximum
    and sum [G * block_q, 128] and weighted values [G * block_q, D], f32.

    Query j of the chunk sits at position start + j and sees key s iff
    s <= start + j. A key block wholly at or before the block's first query
    is folded unmasked; one the block's last query does not reach is not
    folded at all (its operand names the last block that is, and is not
    copied); the one or two between are masked, and their values past the
    last query zeroed: whatever lies past the chunk's end in the row, or past
    the row's end in the operand, is read as nothing, not as 0 x something.

    With `window` (a sliding layer: query t sees key s iff t - window < s <= t)
    the grid's key steps start at the block of the first key the block's first
    query sees (`_window_first_block`), a block is whole only if the block's
    LAST query still sees its first key, and the one or two on the window's
    trailing edge are masked as the diagonal's are, their values before the
    first query's window zeroed.
    """
    b_, iq, ik = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    g, _, d = q_ref.shape
    rows = g * block_q
    first = start_ref[b_] + iq * block_q      # the block's first query's position
    last = first + block_q - 1
    step = ik
    if window is not None:
        ik = ik + _window_first_block(first, window, block_kv)

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def fold(masked: bool):
        q = q_ref[...].reshape(rows, d)
        k, v = k_ref[...], v_ref[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            at = lambda shape, dim: jax.lax.broadcasted_iota(jnp.int32, shape, dim)
            three = (g, block_q, block_kv)
            seen = (ik * block_kv + at(three, 2) <= first + at(three, 1))
            if window is not None:
                seen &= (ik * block_kv + at(three, 2)
                         > first + at(three, 1) - window)
            s = jnp.where(seen.reshape(rows, block_kv), s, -jnp.inf)
            col = ik * block_kv + at(v.shape, 0)
            in_reach = col <= last
            if window is not None:
                in_reach &= col > first - window
            v = jnp.where(in_reach, v, jnp.zeros_like(v))
        # key 0 is seen by every query, so from the first block on the
        # maximum is finite and exp() NaN-free
        m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_safe = m_new
        if window is not None:
            # a late query of the block may see no key of the window's first
            # block: its maximum stays -inf there, and exp() must not see
            # -inf - -inf
            m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        p = jnp.exp(s - m_safe)
        alpha = jnp.exp(m_prev - m_safe)
        l_scr[...] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(p, axis=1, keepdims=True), l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    whole = (ik + 1) * block_kv - 1 <= first
    if window is not None:
        whole &= ik * block_kv > last - window
    pl.when(whole)(lambda: fold(False))
    pl.when(jnp.logical_not(whole) & (ik * block_kv <= last))(
        lambda: fold(True))

    @pl.when(ik == last // block_kv)
    def _finish():
        o_ref[...] = (acc_scr[...] / l_scr[:, :1]).reshape(
            g, block_q, d).astype(o_ref.dtype)


def flash_continuation(
    q: jax.Array,      # [B, T, H, D]: a prefill chunk's queries
    k: jax.Array,      # [B, Kh, S, D]: the row's keys by position, HEAD-MAJOR
    v: jax.Array,      # (or [B, Kh, mp, page, D], `paged_attention.row_pages`)
    start: jax.Array,  # [B] int32: the position of the chunk's first token
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Attention of a prefill chunk that starts at position `start` of its
    row over the row's keys up to its own (`decode_attention`'s mask: query j
    sees key s iff s <= start + j), one flash program; returns [B, T, H, D].

    `start` is a value of the call, prefetched, and so is the grid's length
    along the keys: no key block past start + T - 1 is computed or copied,
    and a row of 9k keys in a table of 40k pays for 9k. Each key block is
    scored once (scores, running maximum and sum and the weighted values in
    one step: bf16 products, f32 accumulation and softmax, the weights cast
    to the values' type), for all query heads of its kv head. The chunk has
    to tile (`continuation_blocks`).

    `window` (a sliding layer): query j sees the last `window` keys up to its
    own. The grid along the keys then starts, for each query block, at the
    block that holds the first key its first query sees, so a block wholly
    before the window gets no copy and no arithmetic and the operand may hold
    anything there; the kernel's name is `flash_continuation_window`."""
    b, t, h, d = q.shape
    kh = k.shape[1]
    k, v = k.reshape(b, kh, -1, d), v.reshape(b, kh, -1, d)
    s_max, g = k.shape[2], h // kh
    block_q = continuation_blocks(t, g, q.dtype)
    if block_q is None:
        raise ValueError(f"flash_continuation: no query block tiles a chunk "
                         f"of {t} x {g} heads of {q.dtype}")
    block_kv = min(_CONT_BLOCK_KV, s_max)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    start = start.astype(jnp.int32)
    n_kv = (jnp.max(start) + t + block_kv - 1) // block_kv
    if window is not None:
        # no query block's keys span more blocks than this
        n_kv = jnp.minimum(n_kv, (window + block_q - 2) // block_kv + 2)

    def keys_of(b_, kh_, iq, ik, start):
        # past the last block the query block reaches: that block again
        reach = (start[b_] + (iq + 1) * block_q - 1) // block_kv
        if window is not None:
            ik = ik + _window_first_block(start[b_] + iq * block_q, window,
                                          block_kv)
        return (b_, kh_, jnp.minimum(ik, reach), 0)

    heads = pl.BlockSpec((None, None, g, block_q, d),
                         lambda b_, kh_, iq, ik, start: (b_, kh_, 0, iq, 0))
    keys = pl.BlockSpec((None, None, block_kv, d), keys_of)
    out = pl.pallas_call(
        functools.partial(_continuation_kernel, scale=scale, block_q=block_q,
                          block_kv=block_kv,
                          **({} if window is None else {"window": window})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kh, t // block_q, n_kv),
            in_specs=[heads, keys, keys],
            out_specs=heads,
            scratch_shapes=[pltpu.VMEM((g * block_q, _LANES), jnp.float32),
                            pltpu.VMEM((g * block_q, _LANES), jnp.float32),
                            pltpu.VMEM((g * block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, t, d), q.dtype),
        interpret=interpret,
        name=("flash_continuation" if window is None
              else "flash_continuation_window"),
    )(start, q.reshape(b, t, kh, g, d).transpose(0, 2, 3, 1, 4), k, v)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, h, d)
