"""Operations and bytes of learned sparse decode attention (a lightning
indexer's top-k selection over a paged cache), from shapes alone (no jax).
`sizes` is the builder's `model_sizes`: `index_dim`, `index_heads`,
`index_topk` beside the attention's own."""


def index_bytes_per_token(s: dict, dtype_bytes: int = 2) -> int:
    """The indexer's one key head of one cached token in every layer."""
    return s["index_dim"] * dtype_bytes * s["n_layers"]


def selected_kv_bytes_per_key(s: dict, dtype_bytes: int = 2) -> int:
    """K and V of one selected key in every layer."""
    return 2 * s["n_kv_heads"] * s["head_dim"] * dtype_bytes * s["n_layers"]


def sparse_decode_bytes(context_keys: int, selected_keys: int, s: dict) -> int:
    """The least HBM traffic of one decode step of the whole stack over rows
    whose contexts hold `context_keys` keys in all, of which `selected_keys`
    are attended: the indexer reads its key of every context token once, and
    attention reads K and V of the selected ones (queries, scores and outputs
    are hundreds of times smaller; the scores never leave the chip's fast
    memory in the least case)."""
    return (context_keys * index_bytes_per_token(s)
            + selected_keys * selected_kv_bytes_per_key(s))


def sparse_decode_flops(context_keys: int, selected_keys: int, s: dict) -> int:
    """Multiply-adds x 2: the indexer's `index_heads` dot products of
    `index_dim` a context key, and QK^T and PV of every head a selected key."""
    index = 2 * s["index_heads"] * s["index_dim"] * context_keys
    attend = 2 * 2 * s["n_heads"] * s["head_dim"] * selected_keys
    return (index + attend) * s["n_layers"]
