"""Bytes of the two kinds of page a model with sliding-window layers keeps (no
jax): a page of the FULL pool holds the keys and values of the layers that
see every key, a page of the WINDOW pool those of the sliding layers, and a
row holds window pages for its last `window` tokens only. `sizes` is the
builder's `model_sizes` (`layer_types` is the pattern, repeated over
`n_layers`; `window` the keys a sliding query sees, its own included).
`flops.kv_bytes_per_token` multiplies by `n_layers` and knows one pool."""


def layers_of(s: dict, kind: str) -> int:
    kinds = s["layer_types"]
    return sum(kinds[i % len(kinds)] == kind for i in range(s["n_layers"]))


def kv_bytes_per_token(s: dict, kind: str, dtype_bytes: int = 2) -> int:
    """K and V of one token in every layer of `kind` ("sliding" or "full")."""
    return 2 * s["n_kv_heads"] * s["head_dim"] * dtype_bytes * layers_of(s, kind)


def visible_keys(length: int, s: dict) -> int:
    """Keys a decode query of a row of `length` tokens (its own included)
    sees in one sliding layer."""
    return min(length, s["window"])


def paged_decode_bytes(window_keys: int, full_keys: int, s: dict) -> int:
    """The least HBM traffic of paged decode attention: `window_keys` and
    `full_keys` are the sums over rows and steps of the keys a query sees in
    ONE sliding and in ONE full layer (min(len, window) and len); each is
    read once, K and V, in every layer of its kind."""
    return (window_keys * kv_bytes_per_token(s, "sliding")
            + full_keys * kv_bytes_per_token(s, "full"))


def live_bytes(window_pages: int, full_pages: int, page_size: int,
               s: dict) -> int:
    """What rows that hold so many pages of each pool hold in bytes."""
    return page_size * (window_pages * kv_bytes_per_token(s, "sliding")
                        + full_pages * kv_bytes_per_token(s, "full"))


def one_pool_bytes(full_pages: int, page_size: int, s: dict) -> int:
    """What the same rows would hold if every layer kept every key: a row
    has a full page for every token."""
    return page_size * full_pages * (kv_bytes_per_token(s, "sliding")
                                     + kv_bytes_per_token(s, "full"))
