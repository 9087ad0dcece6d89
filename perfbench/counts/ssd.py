"""Operations and bytes of the Mamba-2 recurrence (SSD,
`ray_tpu/ops/ssd.py`), from shapes alone (no jax). `sizes` is the builder's
`model_sizes`: `ssm_heads` heads of `ssm_head_dim` with a state of `ssm_state`
a channel, B and C shared by `ssm_groups` groups, in every one of `n_layers`.
What the algorithm must move and compute is counted, not what a program
happens to: the chunked form's masks, exponentials and layout changes are not
in it."""


def mixer_layers(s: dict) -> int:
    """Every layer has a mixer where the model has one."""
    return s["n_layers"] if s.get("ssm_heads") else 0


def state_bytes(s: dict) -> int:
    """One row's recurrent state in one layer, f32."""
    return s["ssm_heads"] * s["ssm_state"] * s["ssm_head_dim"] * 4


def conv_bytes(s: dict, dtype_bytes: int = 2) -> int:
    """The short convolution's carried inputs of one row in one layer."""
    channels = (s["ssm_heads"] * s["ssm_head_dim"]
                + 2 * s["ssm_groups"] * s["ssm_state"])
    return (s["ssm_conv"] - 1) * channels * dtype_bytes


def state_bytes_per_slot(s: dict) -> int:
    """What a slot holds beside its pages, and what a snapshot holds."""
    return mixer_layers(s) * (state_bytes(s) + conv_bytes(s))


def decode_bytes(rows: int, s: dict) -> int:
    """The least HBM traffic of one decode step of every layer's update over
    `rows` live rows: each row's state read and written once a layer (x, dt,
    B, C and the output are a thousandth of it)."""
    return rows * mixer_layers(s) * 2 * state_bytes(s)


def chunked_flops(tokens: int, s: dict, chunk: int = None) -> int:
    """Multiply-adds x 2 that the chunked form needs for `tokens` tokens of
    one row in one layer. A chunk of L tokens: C B^T a GROUP (the lower
    triangle, L^2 N); a head: the masked product with the inputs (the lower
    triangle, L^2 P), the chunk's own contribution to the state and the
    carried state's to the output (2 L N P each)."""
    h, p, n, g = (s["ssm_heads"], s["ssm_head_dim"], s["ssm_state"],
                  s["ssm_groups"])
    c = chunk or s["ssm_chunk"]
    chunks = -(-tokens // c)
    return chunks * (g * c * c * n + h * (c * c * p + 4 * c * n * p))


def chunked_bytes(tokens: int, s: dict) -> int:
    """The least HBM traffic of that call: x (bf16), B and C (bf16 a group)
    and dt (f32 a head) in, the output (f32) out, the state read and written
    once."""
    h, p, n, g = (s["ssm_heads"], s["ssm_head_dim"], s["ssm_state"],
                  s["ssm_groups"])
    per_token = h * p * 2 + 2 * g * n * 2 + h * 4 + h * p * 4
    return tokens * per_token + 2 * state_bytes(s)


def chunked_least_seconds(tokens: int, s: dict, peaks: dict) -> float:
    """The roofline of one layer's prefill call over `tokens` tokens: the
    larger of its operations over the peak bf16 rate and its bytes over the
    peak bandwidth."""
    return max(chunked_flops(tokens, s) / peaks["bf16_flops"],
               chunked_bytes(tokens, s) / peaks["hbm_bytes_per_s"])
