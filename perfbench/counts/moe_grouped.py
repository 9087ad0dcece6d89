"""Operations and bytes of the sorted, dropless grouped expert product, from
shapes alone (no jax). `sizes` is the builder's `model_sizes`: `n_experts`,
`top_k`, `expert_dim` (an expert's width), `d_model`."""


def expert_bytes(s: dict, dtype_bytes: int = 2) -> int:
    """One expert's three matrices."""
    return 3 * s["d_model"] * s["expert_dim"] * dtype_bytes


def experts_touched(rows: int, s: dict) -> float:
    """How many of a layer's experts `rows` routed rows reach, when each row
    falls on any expert alike (seeded normal weights route near enough so):
    E (1 - (1 - 1/E)^rows). 192 rows reach 99.6 of 128 experts, 4096 all."""
    e = s["n_experts"]
    return e * (1.0 - (1.0 - 1.0 / e) ** rows)


def grouped_bytes(tokens: int, s: dict, touched: float = None) -> float:
    """The least HBM traffic of one layer's grouped product over `tokens`
    tokens: the weights of the `touched` experts its rows reach (as counted
    on the device; none given: `experts_touched`), read once, and each routed
    row read and written once (in, the gate/up product's width twice, out)."""
    rows = tokens * s["top_k"]
    activations = rows * 2 * (2 * s["d_model"] + 2 * s["expert_dim"])
    if touched is None:
        touched = experts_touched(rows, s)
    return touched * expert_bytes(s) + activations


def grouped_flops(tokens: int, s: dict) -> int:
    """Multiply-adds x 2 of the three products over the routed rows only."""
    return 2 * 3 * s["d_model"] * s["expert_dim"] * tokens * s["top_k"]


def least_seconds(tokens: int, s: dict, peaks: dict,
                  touched: float = None) -> float:
    """The roofline of one layer's call: the larger of its bytes over peak
    bandwidth and its operations over peak bf16 rate."""
    return max(grouped_bytes(tokens, s, touched) / peaks["hbm_bytes_per_s"],
               grouped_flops(tokens, s) / peaks["bf16_flops"])
