"""Operations of a prefill continuation chunk's attention in a model with
sliding-window layers (no jax): the (query, key) pairs its real queries see,
in one full layer and in one sliding layer, times 4 x heads x head_dim (QK^T
and PV, a multiply and an add each). Padding past the chunk's real tokens and
the masked halves of the diagonal's and the window's edge blocks are not
needed work."""

from perfbench.counts import window_cache


def chunk_query_keys(start: int, n: int, window: int = None) -> int:
    """Pairs the `n` queries at positions start .. start + n - 1 see: query
    j sees start + j + 1 keys, through a window at most `window`."""
    if window is None:
        return n * start + n * (n + 1) // 2
    short = min(n, max(0, window - start - 1))    # queries not yet a window in
    return short * start + short * (short + 1) // 2 + (n - short) * window


def continuation_flops(window_query_keys: int, full_query_keys: int,
                       s: dict) -> int:
    """`window_query_keys` / `full_query_keys`: the pairs in ONE sliding and
    in ONE full layer, summed over the chunks."""
    per_pair = 4 * s["n_heads"] * s["head_dim"]
    return per_pair * (
        window_cache.layers_of(s, "sliding") * window_query_keys
        + window_cache.layers_of(s, "full") * full_query_keys)
