"""The operations and bytes the algorithms need, from shapes alone (no jax):
the yardstick's own arithmetic, so that no PR that claims a gain can change
what a utilization is measured against. `sizes` is a builder's `model_sizes`.

`train_flops_per_token` is a copy of `ray_tpu.models.llama.llama_compute_flops`
(6 x active parameters + causal attention), recomputation not counted.
"""


def _attn_params(s: dict) -> int:
    return s["d_model"] * s["head_dim"] * 2 * (s["n_heads"] + s["n_kv_heads"])


def _mlp_params(s: dict) -> int:
    return 3 * s["d_model"] * s["ffn"]


def param_count(s: dict) -> int:
    layer = _attn_params(s) + 2 * s["d_model"]
    if s["n_experts"]:
        layer += s["n_experts"] * _mlp_params(s) + s["d_model"] * s["n_experts"]
    else:
        layer += _mlp_params(s)
    return s["n_layers"] * layer + 2 * s["vocab"] * s["d_model"] + s["d_model"]


def train_flops_per_token(s: dict, seq: int) -> float:
    """Forward and backward of one token in a sequence of `seq`: 6 per active
    parameter (embedding excluded, head included), plus causal attention
    (2 matmuls forward, 4 backward, half of the square)."""
    ffn = (s["top_k"] * _mlp_params(s) + s["d_model"] * s["n_experts"]
           if s["n_experts"] else _mlp_params(s))
    active = s["n_layers"] * (_attn_params(s) + ffn) + s["vocab"] * s["d_model"]
    attention = 6 * s["n_layers"] * s["n_heads"] * s["head_dim"] * seq
    return 6.0 * active + attention


def flash_train_flops(s: dict, rows: int, seq: int) -> float:
    """What causal flash attention needs for forward and backward over `rows`
    sequences in every layer: 2 matmuls forward (QK^T, PV) and 5 backward
    (QK^T again, dV, dP, dQ, dK), each 2 x seq^2 x head_dim a head, halved by
    causality. A kernel that recomputes more gets no credit for it."""
    per_head = 7 * 2 * seq * seq * s["head_dim"] / 2
    return rows * s["n_layers"] * s["n_heads"] * per_head


def kv_bytes_per_token(s: dict, dtype_bytes: int = 2) -> int:
    """K and V of one token in every layer."""
    return 2 * s["n_kv_heads"] * s["head_dim"] * dtype_bytes * s["n_layers"]


def paged_decode_bytes(context_tokens: int, s: dict) -> int:
    """The least HBM traffic of paged decode attention for one step of the
    whole stack: every cached K and V of every active sequence read once
    (queries and outputs are thousands of times smaller)."""
    return context_tokens * kv_bytes_per_token(s)
