"""The count modules of a model with sliding-window layers, on hand-worked
cases: a row under and over the window, chunks inside, across and past it."""

from perfbench.counts import window_attention, window_cache

# the benchmark's cut: 3 sliding layers to 1 full, 8 kv heads of 128 in bf16
S = dict(n_layers=4, layer_types=("sliding", "sliding", "sliding", "full"),
         n_kv_heads=8, head_dim=128, n_heads=128, window=4096)


def test_bytes_a_token_of_each_pool():
    assert window_cache.layers_of(S, "sliding") == 3
    assert window_cache.layers_of(S, "full") == 1
    assert window_cache.kv_bytes_per_token(S, "full") == 4096
    assert window_cache.kv_bytes_per_token(S, "sliding") == 12288
    # two periods: the pattern repeats
    assert window_cache.layers_of(dict(S, n_layers=8), "sliding") == 6


def test_a_row_under_and_over_the_window():
    assert window_cache.visible_keys(1000, S) == 1000
    assert window_cache.visible_keys(4096, S) == 4096
    assert window_cache.visible_keys(27000, S) == 4096
    # one step of one row of 27,000 keys: 4096 keys in three layers, all in one
    assert window_cache.paged_decode_bytes(4096, 27000, S) == (
        4096 * 12288 + 27000 * 4096)
    # the same row with one pool: every layer reads every key
    assert 27000 * 16384 / window_cache.paged_decode_bytes(4096, 27000, S) > 2.7


def test_live_bytes_against_one_pool():
    # a row of 27,008 tokens: 422 full pages, 81 window pages at most
    live = window_cache.live_bytes(81, 422, 64, S)
    one = window_cache.one_pool_bytes(422, 64, S)
    assert live == 64 * (81 * 12288 + 422 * 4096)
    assert one == 422 * 64 * 16384
    assert round(100 * live / one, 1) == 39.4
    # a row under the window holds a page of each pool for every token
    assert (window_cache.live_bytes(10, 10, 64, S)
            == window_cache.one_pool_bytes(10, 64, S))


def test_a_chunks_pairs_inside_across_and_past_the_window():
    pairs = window_attention.chunk_query_keys
    # no window: query j of a chunk at `start` sees start + j + 1 keys
    assert pairs(0, 4) == 1 + 2 + 3 + 4
    assert pairs(10, 3) == 11 + 12 + 13
    # a window of 5: inside it the same, past it 5 a query
    assert pairs(0, 4, 5) == 1 + 2 + 3 + 4
    assert pairs(2, 6, 5) == 3 + 4 + 5 + 5 + 5 + 5
    assert pairs(100, 7, 5) == 7 * 5
    # by brute force, the mask itself
    for start, n, w in [(0, 9, 4), (3, 8, 6), (20, 5, 7), (6, 1, 7)]:
        want = sum(sum(1 for s in range(start + j + 1) if s > start + j - w)
                   for j in range(n))
        assert pairs(start, n, w) == want, (start, n, w)
    # the benchmark's chunk at 27k keys: a sliding layer a sixth of a full one
    assert pairs(26624, 1024, 4096) == 1024 * 4096
    assert pairs(26624, 1024) / pairs(26624, 1024, 4096) > 6.6


def test_continuation_flops_count_each_kind_of_layer():
    flops = window_attention.continuation_flops(1024 * 4096, 1024 * 27136, S)
    assert flops == 4 * 128 * 128 * (3 * 1024 * 4096 + 1024 * 27136)
