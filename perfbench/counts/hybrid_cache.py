"""Bytes of the two kinds of cache a model with linear-attention layers keeps
(no jax): keys and values a token in its FULL layers only, and a recurrent
state a slot (and a snapshot) in the others. `flops.kv_bytes_per_token`
multiplies by `n_layers`; here one layer in `full_attn_every` has keys and
values. `sizes` is the builder's `model_sizes`."""

from perfbench.counts import kda


def full_layers(s: dict) -> int:
    return s["n_layers"] - kda.linear_layers(s)


def kv_bytes_per_token(s: dict, dtype_bytes: int = 2) -> int:
    """K and V of one token in every layer that has them."""
    return 2 * s["n_kv_heads"] * s["head_dim"] * dtype_bytes * full_layers(s)


def paged_decode_bytes(context_tokens: int, s: dict) -> int:
    """The least HBM traffic of paged decode attention for one step of the
    stack: every cached K and V of every active row read once."""
    return context_tokens * kv_bytes_per_token(s)


def conv_bytes(s: dict, dtype_bytes: int = 2) -> int:
    """The short convolution's carried inputs of one row in one layer."""
    channels = s["linear_heads"] * (2 * s["linear_key_dim"]
                                    + s["linear_value_dim"])
    return (s["linear_conv"] - 1) * channels * dtype_bytes


def state_bytes_per_slot(s: dict) -> int:
    """What a slot holds beside its pages, and what a snapshot holds."""
    return kda.linear_layers(s) * (kda.state_bytes(s) + conv_bytes(s))


def snapshot_worth_tokens(s: dict) -> float:
    """How many tokens of keys and values one snapshot costs."""
    return state_bytes_per_slot(s) / kv_bytes_per_token(s)
