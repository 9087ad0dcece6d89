"""Operations and bytes of gated delta-rule linear attention (KDA,
`ray_tpu/ops/linear_attention.py`), from shapes alone (no jax). `sizes` is the
builder's `model_sizes`: `linear_heads`, `linear_key_dim`, `linear_value_dim`,
and how many of `n_layers` are linear (`full_attn_every`)."""

CHUNK = 64       # the chunked program's chunk, tokens


def linear_layers(s: dict) -> int:
    every = s["full_attn_every"]
    return sum(1 for i in range(s["n_layers"]) if i % every) if every else 0


def state_bytes(s: dict) -> int:
    """One row's recurrent state in one layer, f32."""
    return s["linear_heads"] * s["linear_key_dim"] * s["linear_value_dim"] * 4


def chunked_flops(tokens: int, s: dict, chunk: int = CHUNK) -> int:
    """Multiply-adds x 2 that the chunked form needs for `tokens` tokens of
    one row in one layer. A chunk of C tokens and a head: the strictly lower
    K+ K-^T and the lower Q+ K-^T (C^2 dk each, the triangle being half of
    2 C^2 dk), the unit-triangular inverse (C^3 / 3), T K+ and T V (C^2 dk,
    C^2 dv: T is triangular), and against the carried state W S, Q+ S and
    K-^T U (2 C dk dv each) and tril(QK) U (C^2 dv)."""
    h, dk, dv = s["linear_heads"], s["linear_key_dim"], s["linear_value_dim"]
    n = -(-tokens // chunk)
    c = chunk
    per_head = (2 * c * c * dk + c ** 3 // 3 + c * c * (dk + dv)
                + 6 * c * dk * dv + c * c * dv)
    return n * h * per_head


def chunked_bytes(tokens: int, s: dict) -> int:
    """The least HBM traffic of that call: q, k, v in (bf16), the log-decay
    (f32 a key channel) and beta in, the output (f32) out, the state read and
    written once."""
    h, dk, dv = s["linear_heads"], s["linear_key_dim"], s["linear_value_dim"]
    per_token = h * ((2 * dk + dv) * 2 + dk * 4 + 4 + dv * 4)
    return tokens * per_token + 2 * state_bytes(s)


def chunked_least_seconds(tokens: int, s: dict, peaks: dict) -> float:
    """The roofline of one layer's prefill call over `tokens` tokens: the
    larger of its operations over the peak bf16 rate and its bytes over the
    peak bandwidth."""
    return max(chunked_flops(tokens, s) / peaks["bf16_flops"],
               chunked_bytes(tokens, s) / peaks["hbm_bytes_per_s"])


def decode_bytes(rows: int, s: dict) -> int:
    """The least HBM traffic of one decode step of every linear layer over
    `rows` rows: each row's state read and written once a layer (q, k, v and
    the output are a thousandth of it)."""
    return rows * linear_layers(s) * 2 * state_bytes(s)
