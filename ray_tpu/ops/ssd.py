"""The Mamba-2 recurrence (state-space duality, arXiv:2405.21060): a scalar
decay a head over a fixed state, as a chunked program for prefill and a
one-token update for decode. Beside `ops/linear_attention.py`, whose gated
delta rule has a decay a key channel and a triangular solve; this one has
neither, and its B and C are shared by a group of heads.

For one head of width P with a state of N a channel, the state S [N, P]
(state-dimension major: the layout the decode kernel reads, KDA's [dk, dv]
with B and C in the key's place and x in the value's) moves a token at a time:

    S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T
    y_t = S_t^T C_t + D x_t

with dt_t > 0 the token's step (after the softplus), A < 0 the head's scalar,
and B_t, C_t [N] those of the head's group.

The chunked form (`ssd_chunked`). With a_t = dt_t A and G_t its running sum
inside a chunk of L tokens that starts from S_0:

    y_t = sum_{s <= t} (C_t . B_s) exp(G_t - G_s) dt_s x_s + exp(G_t) S_0^T C_t
    S_L = exp(G_L) S_0 + sum_s exp(G_L - G_s) B_s (dt_s x_s)^T

Every exponent is of a difference G_t - G_s <= 0 with s <= t, so nothing here
can overflow. What does not depend on S_0 (the masked C B^T, its product with
the inputs, each chunk's own contribution to the state) is computed for all
chunks at once; a `lax.scan` over the chunks carries the state through two
products a chunk. Everything is f32: the state and its update are held in f32
whatever the activations' type.

Tokens past `n_valid` (the padding of a prefill bucket, a slot that does not
decode this step) take dt = 0: they decay nothing and write nothing, so the
state a call returns is the state after `n_valid` tokens.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128
_HI = jax.lax.Precision.HIGHEST
_HEADS_A_BLOCK = 8      # heads of one row a grid step of the decode kernel


def _step_kernel(xdt_ref, decay_ref, b_ref, c_ref, s_ref, y_ref, s_out_ref):
    """One row's block of heads, all of one group: the state is read once and
    written once. xdt [1, hb, P] (dt x); decay [1, hb, P] (exp(dt A) along
    the lanes: a [1, 1] value does not broadcast both ways); B, C
    [1, 1, 1, N], the group's; state [1, hb, N, P]. A state channel lies
    along the state's sublanes: B and C are turned into columns once a block,
    and the read-out is a sum over sublanes that leaves a head's row of P."""
    n = b_ref.shape[-1]
    col = lambda ref: jnp.broadcast_to(ref[0, 0], (8, n)).T[:, :1]   # [N, 1]
    b, c = col(b_ref), col(c_ref)
    xdt, decay = xdt_ref[0], decay_ref[0]
    for h in range(xdt.shape[0]):
        new = s_ref[0, h] * decay[h:h + 1] + b * xdt[h:h + 1]     # [N, P]
        y_ref[0, h:h + 1] = jnp.sum(new * c, axis=0, keepdims=True)
        s_out_ref[0, h] = new


def _ssd_step_kernel(xdt, decay, b, c, state, interpret=False):
    """`ssd_step`'s update as one pallas kernel: grid (rows, blocks of
    heads), the state aliased to its output, so a step moves each row's state
    once in and once out."""
    rows, h, p = xdt.shape
    g, n = b.shape[1:]
    hb = _HEADS_A_BLOCK
    per_group = h // g
    row = lambda w: pl.BlockSpec((1, hb, w), lambda i, j: (i, j, 0))
    group = pl.BlockSpec((1, 1, 1, n),
                         lambda i, j: (i, j * hb // per_group, 0, 0))
    whole = pl.BlockSpec((1, hb, n, p), lambda i, j: (i, j, 0, 0))
    return pl.pallas_call(
        _step_kernel,
        grid=(rows, h // hb),
        in_specs=[row(p), row(p), group, group, whole],
        out_specs=[row(p), whole],
        out_shape=[jax.ShapeDtypeStruct((rows, h, p), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={4: 1},
        interpret=interpret,
        name="ssd_decode",
    )(xdt, jnp.broadcast_to(decay[..., None], xdt.shape), b[:, :, None],
      c[:, :, None], state)


def ssd_step(x, dt, a, b, c, d, state, valid=None, interpret=None):
    """One token a row (decode). x [B, H, P]; dt [B, H] (after the softplus);
    a, d [H]; b, c [B, G, N]; state [B, H, N, P] f32; valid [B] bool or None:
    rows whose state moves (the others take dt = 0, which is exactly no
    move). Returns (y [B, H, P] f32, state'). On the TPU (and where
    `interpret` says so) a pallas kernel, where a block of heads lies in one
    group and a head's state is whole tiles; XLA's operations elsewhere."""
    f32 = jnp.float32
    rows, h, p = x.shape
    g, n = b.shape[1:]
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    if valid is not None:
        dt = jnp.where(valid[:, None], dt, 0.0)
    xdt, decay = x * dt[..., None], jnp.exp(dt * a)
    kernel = interpret is not None or jax.default_backend() == "tpu"
    if (kernel and h % _HEADS_A_BLOCK == 0 and n % 128 == 0 and p % 128 == 0
            and (h // g) % _HEADS_A_BLOCK == 0):
        y, state = _ssd_step_kernel(xdt, decay, b, c, state.astype(f32),
                                    interpret=bool(interpret))
    else:
        with jax.named_scope("ssd_decode"):
            heads = lambda v: jnp.repeat(v, h // g, axis=1)       # [B, H, N]
            state = (state * decay[..., None, None]
                     + heads(b)[..., None] * xdt[..., None, :])
            y = jnp.einsum("bhnp,bhn->bhp", state, heads(c), precision=_HI)
    return y + d[:, None] * x, state


_HEADS_A_CHUNK_STEP = 4    # heads of one group a grid step of the prefill kernel


def _chunk_kernel(x_ref, dt_ref, gc_ref, last_ref, b_ref, c_ref, s0_ref,
                  y_ref, s_out_ref, s_scr):
    """Step (row, block of heads, chunk): the chunk's outputs for `hb` heads
    of one group from the state the chunk before left in `s_scr`, and the
    state it leaves. x [1, hb, L, P]; dt, gc (the running sum of dt A inside
    the chunk) [1, hb, 1, 1, L]; last (gc's last value, along the lanes: a
    [1, 1] value does not broadcast both ways) [1, hb, 1, 1, P]; B, C
    [1, 1, L, N], the group's; s0, s_out [1, hb, N, P]. The group's C B^T is
    one product a step; a head's mask exp(G_t - G_s), s <= t, is made here
    from the row of its running sums and that row turned into a column."""
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _start():
        s_scr[...] = s0_ref[0]

    f32 = jnp.float32
    dot = lambda a, b, dims: jax.lax.dot_general(
        a, b, (dims, ((), ())), precision=_HI, preferred_element_type=f32)
    b, c = b_ref[0, 0], c_ref[0, 0]                              # [L, N]
    length = b.shape[0]
    cb = dot(c, b, ((1,), (1,)))                                 # [L, L]
    lower = (jax.lax.broadcasted_iota(jnp.int32, (length, length), 1)
             <= jax.lax.broadcasted_iota(jnp.int32, (length, length), 0))
    column = lambda row: jnp.broadcast_to(row, (8, length)).T[:, :1]
    for h in range(x_ref.shape[1]):
        gc_row = gc_ref[0, h, 0]                                 # [1, L]
        gc_col = column(gc_row)                                  # [L, 1]
        last = last_ref[0, h, 0]                                 # [1, P]
        xdt = x_ref[0, h] * column(dt_ref[0, h, 0])              # [L, P]
        seg = jnp.exp(jnp.where(lower, gc_col - gc_row, -jnp.inf))
        s = s_scr[h]                                             # [N, P]
        y_ref[0, h] = (dot(cb * seg, xdt, ((1,), (0,)))
                       + jnp.exp(gc_col) * dot(c, s, ((1,), (0,))))
        to_end = jnp.exp(last[:, :1] - gc_col)                   # [L, 1]
        s_scr[h] = jnp.exp(last) * s + dot(b, xdt * to_end, ((0,), (0,)))

    @pl.when(ci == pl.num_programs(2) - 1)
    def _end():
        s_out_ref[0] = s_scr[...]


def _ssd_chunk_kernel(x, dt, gc, b, c, state, chunk, interpret=False):
    """The chunked form as one pallas kernel, `ssd_prefill`: grid (rows,
    blocks of heads, chunks), the chunks in order with the state carried in
    VMEM. x [B, H, T, P]; dt, gc [B, H, T]; b, c [B, G, T, N]; state
    [B, H, N, P]; T a multiple of `chunk`. Returns (y [B, H, T, P], state')."""
    rows, h, t, p = x.shape
    g, n = b.shape[1], b.shape[-1]
    hb, m, per = _HEADS_A_CHUNK_STEP, t // chunk, h // g
    by_chunk = lambda v: v.reshape(rows, h, m, 1, chunk)
    last = jnp.broadcast_to(gc.reshape(rows, h, m, chunk)[..., -1:, None],
                            (rows, h, m, 1, p))
    heads = lambda w: pl.BlockSpec((1, hb, chunk, w),
                                   lambda i, j, k: (i, j, k, 0))
    rows_of = lambda w: pl.BlockSpec((1, hb, 1, 1, w),
                                     lambda i, j, k: (i, j, k, 0, 0))
    group = pl.BlockSpec((1, 1, chunk, n),
                         lambda i, j, k: (i, j * hb // per, k, 0))
    whole = pl.BlockSpec((1, hb, n, p), lambda i, j, k: (i, j, 0, 0))
    return pl.pallas_call(
        _chunk_kernel,
        grid=(rows, h // hb, m),
        in_specs=[heads(p), rows_of(chunk), rows_of(chunk), rows_of(p),
                  group, group, whole],
        out_specs=[heads(p), whole],
        out_shape=[jax.ShapeDtypeStruct((rows, h, t, p), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_prefill",
    )(x, by_chunk(dt), by_chunk(gc), last, b, c, state)


def ssd_chunked(x, dt, a, b, c, d, state, n_valid=None, chunk: int = CHUNK,
                interpret=None):
    """A stretch of T tokens a row (prefill), from `state` on. x [B, T, H, P];
    dt [B, T, H] (after the softplus); a, d [H]; b, c [B, T, G, N]; state
    [B, H, N, P] f32; n_valid [B] or None. Returns (y [B, T, H, P] f32, the
    state after n_valid tokens). On the TPU (and where `interpret` says so) a
    pallas kernel, where its blocks of heads lie in one group and a chunk, a
    head and a state are whole tiles; XLA's operations elsewhere."""
    f32 = jnp.float32
    rows, t, h, p = x.shape
    g, n = b.shape[2:]
    x, dt, b, c = (v.astype(f32) for v in (x, dt, b, c))
    if n_valid is not None:
        real = jnp.arange(t)[None] < n_valid[:, None]              # [B, T]
        dt = jnp.where(real[..., None], dt, 0.0)
    m = -(-t // chunk)
    pad = m * chunk - t
    skip = d[:, None] * x
    if pad:
        x, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for v in (x, b, c))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    kernel = interpret is not None or jax.default_backend() == "tpu"
    if (kernel and chunk % 128 == 0 and p % 128 == 0 and n % 128 == 0
            and (h // g) % _HEADS_A_CHUNK_STEP == 0):
        # head-major, and the running sum of dt A inside each chunk
        gc = jnp.cumsum((dt * a).reshape(rows, m, chunk, h), axis=2)
        major = lambda v: v.swapaxes(1, 2)
        y, state = _ssd_chunk_kernel(
            major(x), major(dt), major(gc.reshape(rows, m * chunk, h)),
            major(b), major(c), state.astype(f32), chunk,
            interpret=bool(interpret))
        return major(y)[:, :t] + skip, state
    with jax.named_scope("ssd_prefill"):
        # [B, m, H | G, L, .]: chunks and heads are batch dimensions
        cut = lambda v: v.reshape(rows, m, chunk, v.shape[2], -1).swapaxes(2, 3)
        xdt = cut(x * dt[..., None])                               # [B,m,H,L,P]
        b, c = cut(b), cut(c)                                      # [B,m,G,L,N]
        gc = jnp.cumsum(cut(dt * a)[..., 0], axis=-1)              # [B,m,H,L]
        lower = jnp.tril(jnp.ones((chunk, chunk), bool))
        # exp of a masked difference: the upper triangle's would be > 0
        seg = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                                -jnp.inf))                         # [B,m,H,L,L]
        cb = jnp.einsum("bmgtn,bmgsn->bmgts", c, b, precision=_HI)
        per = h // g
        grouped = lambda v: v.reshape(rows, m, g, per, *v.shape[3:])
        y_in = jnp.einsum("bmgkts,bmgksp->bmgktp",
                          cb[:, :, :, None] * grouped(seg), grouped(xdt),
                          precision=_HI)
        to_end = jnp.exp(gc[..., -1:] - gc)                        # [B,m,H,L]
        own = jnp.einsum("bmgsn,bmgksp->bmgknp", b,
                         grouped(xdt * to_end[..., None]), precision=_HI)
        from_start = grouped(jnp.exp(gc))[..., None]               # [B,m,G,k,L,1]
        whole = jnp.exp(gc[..., -1])                               # [B,m,H]

        def step(s, xs):
            c, from_start, own, whole = xs
            y = from_start * jnp.einsum(
                "bgtn,bgknp->bgktp", c, s.reshape(rows, g, per, n, p),
                precision=_HI)
            s = whole[..., None, None] * s + own.reshape(rows, h, n, p)
            return s, y

        lead = lambda v: jnp.moveaxis(v, 1, 0)     # the scan runs over chunks
        state, y_off = jax.lax.scan(
            step, state.astype(f32),
            tuple(lead(v) for v in (c, from_start, own, whole)))
        y = (y_in + jnp.moveaxis(y_off, 0, 1)).reshape(rows, m, h, chunk, p)
        y = y.swapaxes(2, 3).reshape(rows, m * chunk, h, p)[:, :t]
    return y + skip, state


def ssd_recurrent(x, dt, a, b, c, d, state):
    """The recurrence itself, a token at a time (`ssd_step` under a scan):
    what the chunked form has to equal. Same arguments as `ssd_chunked`."""
    def step(s, xs):
        y, s = ssd_step(*xs[:2], a, *xs[2:], d, s)
        return s, y
    state, y = jax.lax.scan(step, state.astype(jnp.float32), tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1), state
