"""Operations and bytes of the grouped expert product on a chip that holds a
SHARE of the experts (no jax): the router scores `n_experts` and keeps
`top_k` a token, the bank holds `experts_held` and computes the pairs that
fall on those, one in n_experts / experts_held when routing is even.
`counts/moe_grouped.py` counts tokens x top_k rows, all of them held."""


def expert_bytes(s: dict, dtype_bytes: int = 2) -> int:
    return 3 * s["d_model"] * s["expert_dim"] * dtype_bytes


def held_pairs(tokens: int, s: dict) -> float:
    """(token, expert) pairs of `tokens` tokens that fall on a held expert,
    when every expert is as likely as another."""
    return tokens * s["top_k"] * s["experts_held"] / s["n_experts"]


def experts_touched(pairs: float, s: dict) -> float:
    """How many of the held experts `pairs` such pairs reach."""
    e = s["experts_held"]
    return e * (1.0 - (1.0 - 1.0 / e) ** pairs)


def share_bytes(pairs: float, s: dict, touched: float = None) -> float:
    """The least HBM traffic of one layer's call: the weights of the held
    experts its pairs reach (as counted on the device; none given:
    `experts_touched`), read once, and each pair's row read and written."""
    if touched is None:
        touched = experts_touched(pairs, s)
    return (touched * expert_bytes(s)
            + pairs * 2 * (2 * s["d_model"] + 2 * s["expert_dim"]))


def share_flops(pairs: float, s: dict) -> float:
    return 2 * 3 * s["d_model"] * s["expert_dim"] * pairs


def least_seconds(pairs: float, s: dict, peaks: dict,
                  touched: float = None) -> float:
    return max(share_bytes(pairs, s, touched) / peaks["hbm_bytes_per_s"],
               share_flops(pairs, s) / peaks["bf16_flops"])
