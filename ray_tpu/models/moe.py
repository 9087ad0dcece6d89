"""Mixture-of-experts FFN for the Llama family (Mixtral layout).

Reference parity: the reference serves MoE checkpoints (Mixtral et al.)
through its vLLM/SGLang engines, whose CUDA kernels do scatter/gather
token routing (e.g. python/ray/llm/_internal/serve/engines/sglang/
sglang_engine.py engine wrapper). Two expert products share one parameter
tree (`router`, `w_gate`, `w_up`, `w_down` with a leading [E, ...] dim that
`parallel.sharding.llama_rules()` maps to the `ep` mesh axis), and which one
a model takes follows from its shapes:

- FEW LARGE experts (E / top_k <= 4, Mixtral's 8 / 2): the GShard/Switch
  dense-dispatch formulation, one-hot dispatch/combine tensors contracted
  with einsums, which XLA turns into large static-shape matmuls on the MXU;
  under pjit the dispatch einsum becomes the token all-to-all over ICI,
  inserted by the compiler (scaling-book recipe). Each expert processes at
  most C = ceil(top_k * S / E * capacity_factor) tokens (S = B*T tokens in
  the step, a static shape); tokens over budget are dropped, their combine
  weight is zero and the residual carries them through: the standard Switch
  behavior, and what training runs. SERVING is dropless: `LLMServer` raises
  capacity_factor to E / top_k, so C = S. That computes E / top_k times the
  rows the tokens need, which is the price of the static shapes.
- MANY SMALL experts (E / top_k > 4: 128 / 8 would compute 16 times the
  rows): a sorted, dropless GROUPED product. The S * top_k (token, expert)
  rows are sorted by expert and each expert multiplies its own contiguous
  group (`jax.lax.ragged_dot`: on the TPU a grouped-matmul kernel that reads
  an expert's weights once and computes the routed rows only). No capacity,
  no dropped token, in training and serving alike.

Load balancing: the Switch aux loss E * Σ_e f_e · P_e (f_e = fraction of
tokens whose top-1 choice is e, P_e = mean router prob) is sowed into the
"losses" collection as "moe_aux"; training code collects it with
`model.apply(..., mutable=["losses"])` and adds
`cfg.router_aux_weight * mean(aux)` to the task loss.
"""

import math

import flax.linen as nn
import jax
import jax.numpy as jnp


class MoEMLP(nn.Module):
    """Top-k routed SwiGLU expert bank; drop-in for llama.MLP ([B,T,D] →
    [B,T,D])."""

    cfg: "LlamaConfig"  # noqa: F821 - llama.py owns the config class

    @nn.compact
    def __call__(self, x, real=None):
        """`real` [B, T] bool (optional): the rows that are tokens of a
        request, for the counts a bank with a share of the experts sows."""
        cfg = self.cfg
        E, K = cfg.n_experts, cfg.moe_top_k
        B, T, D = x.shape
        S = B * T
        F = cfg.expert_dim or cfg.ffn_dim
        xf = x.reshape(S, D)

        # Router runs in f32: tiny compute, and bf16 softmax noise here
        # flips expert assignments (standard practice, e.g. Mixtral).
        logits = nn.Dense(E, use_bias=False, dtype=jnp.float32,
                          param_dtype=jnp.float32,
                          kernel_init=nn.initializers.normal(0.02),
                          name="router")(xf.astype(jnp.float32))
        probs = (jax.nn.sigmoid(logits) if cfg.router_score == "sigmoid"
                 else jax.nn.softmax(logits, axis=-1))     # [S, E]
        gate_vals, gate_idx = jax.lax.top_k(probs, K)       # [S, K]
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)         # renormalize

        # Switch load-balance aux loss (top-1 assignment fractions)
        f_e = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E,
                                      dtype=jnp.float32), axis=0)
        p_e = jnp.mean(probs, axis=0)
        self.sow("losses", "moe_aux", E * jnp.sum(f_e * p_e))

        init = nn.initializers.normal(0.02)
        if cfg.experts_held or cfg.n_shared_experts:
            return self._share(xf, gate_vals, gate_idx, real).reshape(B, T, D)
        w_gate = self.param("w_gate", init, (E, D, F), cfg.param_dtype)
        w_up = self.param("w_up", init, (E, D, F), cfg.param_dtype)
        w_down = self.param("w_down", init, (E, F, D), cfg.param_dtype)
        if grouped_product(E, K):
            # how many experts this call's rows reach (their weights are the
            # least a grouped product reads): collected by a caller that
            # asks for the "moe_stats" collection, a no-op otherwise
            self.sow("moe_stats", "experts_touched", jnp.zeros(
                (E,), bool).at[gate_idx.reshape(-1)].set(True).sum(),
                reduce_fn=lambda _, new: new,     # this call's, whatever an
                init_fn=lambda: jnp.int32(0))     # `init` left in the tree
            with jax.named_scope("moe_grouped"):
                y = grouped_experts(xf.astype(cfg.dtype), gate_vals, gate_idx,
                                    w_gate.astype(cfg.dtype),
                                    w_up.astype(cfg.dtype),
                                    w_down.astype(cfg.dtype))
            return y.reshape(B, T, D)

        # Position of each (token, k) assignment inside its expert's queue,
        # k-major (all first choices claim capacity before any second
        # choice — GShard priority). Static shapes throughout.
        C = max(1, math.ceil(cfg.capacity_factor * K * S / E))
        sel = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)  # [S, K, E]
        selk = sel.transpose(1, 0, 2).reshape(K * S, E)
        pos = jnp.cumsum(selk, axis=0) - selk               # [K*S, E]
        posk = (pos.reshape(K, S, E) *
                sel.transpose(1, 0, 2)).sum(-1)             # [K, S]
        keep = (posk < C).astype(jnp.float32)               # over-budget → 0
        gates = gate_vals.T * keep                          # [K, S]

        # combine[s, e, c]: gate weight of token s at slot c of expert e
        combine = jnp.einsum(
            "ks,kse,ksc->sec", gates,
            sel.transpose(1, 0, 2).astype(jnp.float32),
            jax.nn.one_hot(posk, C, dtype=jnp.float32))
        dispatch = (combine > 0).astype(cfg.dtype)          # [S, E, C]

        # Expert bank as single [E, ...] tensors: batched einsums keep the
        # MXU busy and give the sharding engine one leading dim to slice
        # over `ep`.
        expert_in = jnp.einsum("sec,sd->ecd", dispatch,
                               xf.astype(cfg.dtype))        # [E, C, D]
        h = jnp.einsum("ecd,edf->ecf", expert_in,
                       w_gate.astype(cfg.dtype))
        u = jnp.einsum("ecd,edf->ecf", expert_in,
                       w_up.astype(cfg.dtype))
        out = jnp.einsum("ecf,efd->ecd", nn.silu(h) * u,
                         w_down.astype(cfg.dtype))          # [E, C, D]
        y = jnp.einsum("sec,ecd->sd", combine.astype(cfg.dtype), out)
        return y.reshape(B, T, D)


    def _share(self, xf, gate_vals, gate_idx, real):
        """A chip's share of the bank: `experts_held` experts from
        `experts_first` on, through the grouped product. The router has
        scored all E and kept K a token; the pairs that fall on a held expert
        are computed here under their gates (normalised over all K), the
        others are another chip's and add nothing. Every shared expert is
        whole on every chip (one concatenated bank: its product is their sum,
        or with `shared_average` their mean). Nothing stands in for the absent chips: what
        this returns is this chip's part of the layer's sum."""
        cfg = self.cfg
        D = xf.shape[-1]
        F = cfg.expert_dim or cfg.ffn_dim
        held = cfg.experts_held or cfg.n_experts
        init = nn.initializers.normal(0.02)
        w_gate = self.param("w_gate", init, (held, D, F), cfg.param_dtype)
        w_up = self.param("w_up", init, (held, D, F), cfg.param_dtype)
        w_down = self.param("w_down", init, (held, F, D), cfg.param_dtype)
        local = gate_idx - cfg.experts_first
        mine = (local >= 0) & (local < held)                # [S, K]
        local = jnp.where(mine, local, held)       # the others sort last
        keep = lambda _, new: new
        self.sow("moe_stats", "experts_touched", jnp.zeros(
            (held + 1,), bool).at[local.reshape(-1)].set(True)[:held].sum(),
            reduce_fn=keep, init_fn=lambda: jnp.int32(0))
        # pairs on a held expert: of real tokens, and of every row the call
        # multiplies (a bucket's padding and idle slots route too)
        counted = mine if real is None else mine & real.reshape(-1, 1)
        self.sow("moe_stats", "held_pairs",
                 jnp.stack([counted.sum(), mine.sum()]).astype(jnp.int32),
                 reduce_fn=keep, init_fn=lambda: jnp.zeros((2,), jnp.int32))
        x = xf.astype(cfg.dtype)
        with jax.named_scope("moe_grouped"):
            y = grouped_experts(x, gate_vals, local, w_gate.astype(cfg.dtype),
                                w_up.astype(cfg.dtype),
                                w_down.astype(cfg.dtype), held=mine)
        if cfg.n_shared_experts:
            with jax.named_scope("moe_shared"):
                Fs = F * cfg.n_shared_experts
                dense = lambda n, name: nn.Dense(
                    n, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, kernel_init=init, name=name)
                shared = dense(D, "shared_down")(
                    nn.silu(dense(Fs, "shared_gate")(x))
                    * dense(Fs, "shared_up")(x))
                if cfg.shared_average:
                    # the concatenated bank's product is the experts' SUM
                    shared = shared * (1.0 / cfg.n_shared_experts)
                y = y + shared
        return y


def grouped_product(n_experts: int, top_k: int) -> bool:
    """Whether a bank of these shapes takes the grouped product: where the
    dropless one-hot dispatch would compute more than 4 times the routed
    rows."""
    return n_experts > 4 * top_k


_GMM_RHS_TILE_BYTES = 4 << 20      # one tile of an expert's matrix in fast memory
_GMM_EDGE_ROWS = 128    # the row tile of a product whose groups lie on tile edges

# "m x k x n" -> [tm, tk, tn] (None: `jax.lax.ragged_dot`) of every grouped
# product traced in this process: a memo of a function of the shapes and the
# backend, written while tracing and read by `LLMServer.stats()`.
_TILINGS_TAKEN = {}


def gmm_tilings() -> dict:
    """The tiling each grouped product shape traced in this process took. A
    tile's k under the product's k says the experts' matrices are k-tiled:
    read once a VISIT (`_gmm_tiling`)."""
    return dict(_TILINGS_TAKEN)


def _gmm_tiling(m: int, k: int, n: int, itemsize: int):
    """megablox tiles (rows, k, n) for a product of [m, k] rows with [k, n]
    matrices: an expert's matrix whole where it fits fast memory's budget
    (`_GMM_RHS_TILE_BYTES`), else its longer side halved until a tile does (a
    4096 x 1280 matrix goes through as four tiles of 1024 x 1280, 4096 x 4096
    as eight of 1024 x 2048); None where the rows or the sides do not divide.

    What a tiled matrix costs: `gmm`'s grid is (n tiles, visits, k tiles), a
    visit being one (row tile, group) pair and k INNERMOST, and the pipeline
    fetches a block when its index changes. The matrix's block is (group,
    k tile, n tile): whole, or tiled along n alone, it stays put while
    consecutive visits stay in one group, so the product reads each reached
    matrix once. With more than one k tile it changes at every grid step and
    every VISIT streams the group's whole matrix again: read once a group
    only where the group lies inside one row tile, as a decode step's rows
    do, and as `grouped_experts` lays a chunk's."""
    if m % 8:
        return None
    tk, tn = k, n
    while tk * tn * itemsize > _GMM_RHS_TILE_BYTES:
        if tk >= tn and tk % 256 == 0:
            tk //= 2
        elif tn % 256 == 0:
            tn //= 2
        else:
            return None
    return next(t for t in (64, 32, 16, 8) if m % t == 0), tk, tn


def _grouped_dot(lhs, rhs, sizes, tm=None):
    """lhs [M, k] (rows in group order) x rhs [G, k, n] -> [M, n], each group
    of `sizes` by its own matrix. `jax.lax.ragged_dot`, except where the chip
    measured better: on the TPU, rows a multiple of 8 take megablox's pallas
    `gmm` tiled by `_gmm_tiling` (`tm`: the row tile the caller laid its
    groups by): 0.62-0.66 ms a product of 128 experts of 2048 x 768 at 192
    and 4096 rows against `ragged_dot`'s 0.95 and 1.94 (v5e, PR 28; the
    weights alone are 0.49 ms at the chip's bandwidth). Rows past the groups'
    sum (a bank that holds a share of the experts sorts the others' pairs
    there) are no group's: `gmm` visits no tile for them and leaves their
    output rows unwritten, so the caller must not read them."""
    m, k = lhs.shape
    n = rhs.shape[-1]
    tiling = (_gmm_tiling(m, k, n, rhs.dtype.itemsize)
              if jax.default_backend() == "tpu" else None)
    if tiling is not None and tm is not None:
        tiling = (tm,) + tiling[1:]
    _TILINGS_TAKEN[f"{m} x {k} x {n}"] = tiling and list(tiling)
    if tiling is not None:
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        return gmm(lhs, rhs, sizes, preferred_element_type=lhs.dtype,
                   tiling=tiling)
    return jax.lax.ragged_dot(lhs, rhs, sizes)


def grouped_experts(x, gate_vals, gate_idx, w_gate, w_up, w_down, held=None):
    """Sorted, dropless grouped SwiGLU experts. x [S, D]; gate_vals (f32)
    and gate_idx [S, K]: each token's gates and experts; w_gate / w_up
    [E, D, F], w_down [E, F, D]. Returns [S, D] in x.dtype.

    The S * K routed rows are put in expert order (a stable sort, so a run
    is deterministic), each expert multiplies its contiguous group, and the
    rows go back to token order to be summed under their gates in f32.

    `held` [S, K] bool (a bank that holds a share of the experts): the pairs
    to compute. The others carry the index E, past the last group: they sort
    behind every group, no group multiplies them (what the product leaves in
    their rows is not read), and they add nothing to the sum.

    Such a bank's rows are mostly the others', so where a matrix goes to the
    kernel in k tiles (`_gmm_tiling`: every (row tile, group) visit reads
    all of it) and the rows are at least two tiles a group, each group is
    laid on a row-tile edge: `sizes` rounded up to `_GMM_EDGE_ROWS`, the
    buffer longer by a tile a group so that the worst routing (every pair
    held) still fits. A group of up to a tile's rows is then ONE visit, where
    some 64 sorted rows that start anywhere are two. The rows of padding hold
    token 0 and are multiplied; no pair reads them back. (Two tiles a group,
    not one: the gathers, `silu(h) * u` and the index scatter round the
    products work on the padding too, and at 40 experts of 4096 x 1280 on
    8192 rows, padding of five eighths, they took back in the cell what the
    products saved: v5e, PR 43.)"""
    S, K = gate_idx.shape
    E = w_gate.shape[0]
    flat = gate_idx.reshape(-1)
    order = jnp.argsort(flat)                         # routed row -> sorted
    sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    token, rows, tm = order // K, None, None          # sorted row -> its token
    matrix_bytes = math.prod(w_gate.shape[1:]) * w_gate.dtype.itemsize
    if (held is not None and S * K >= 2 * E * _GMM_EDGE_ROWS
            and matrix_bytes > _GMM_RHS_TILE_BYTES):
        tm = _GMM_EDGE_ROWS
        padded = (sizes + tm - 1) // tm * tm
        # the padding ahead of each group; ahead of the others' pairs, all
        ahead = jnp.cumsum(jnp.pad(padded - sizes, (1, 0)))
        rows = jnp.arange(S * K) + ahead[flat[order]]   # sorted -> buffer row
        token = jnp.zeros(-(-S * K // tm) * tm + E * tm, token.dtype).at[
            rows].set(token, indices_are_sorted=True, unique_indices=True)
        sizes = padded
    xs = x[token]                                     # [S*K (+ E*tm), D]
    h = _grouped_dot(xs, w_gate, sizes, tm)
    u = _grouped_dot(xs, w_up, sizes, tm)
    out = _grouped_dot(nn.silu(h) * u, w_down, sizes, tm)
    # (the iota made HERE where no group moved: the program other banks
    # lowered to before is the program they lower to)
    back = jnp.zeros_like(order).at[order].set(      # routed row -> its row
        jnp.arange(S * K) if rows is None else rows)
    out = out[back].reshape(S, K, -1).astype(jnp.float32)
    out = out * gate_vals[..., None]
    if held is not None:
        out = jnp.where(held[..., None], out, 0.0)
    return out.sum(1).astype(x.dtype)


def moe_aux_loss(losses_collection, weight: float) -> jnp.ndarray:
    """Mean sowed router aux loss × weight; 0.0 when the model is dense."""
    vals = jax.tree_util.tree_leaves(losses_collection)
    if not vals:
        return jnp.float32(0.0)
    return weight * sum(jnp.mean(v) for v in vals) / len(vals)
