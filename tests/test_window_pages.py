"""Two kinds of page under the one `PageManager`: the window pool of a model
with sliding-window layers beside the full pool. Pages handed back as a row
advances and never one a live query sees; admission when one pool has room
and the other has not; a prefix match cut back to a depth the window pool
still holds, and a miss; both pools evict; `num_pages` / `free_pages` keep
describing the full pool. Host bookkeeping only: no jax."""

import pytest

from ray_tpu.serve.radix_cache import PageManager

PS, WINDOW = 4, 16            # pages of 4 tokens, a window of 4 pages
BUDGET = (WINDOW + 8 - 2) // PS + 2     # a chunk of 8: 7 pages a row at most


def manager(full=64, win=24, slots=4, **kw):
    return PageManager(full, PS, slots, 32, window_pages=win, window=WINDOW,
                       window_budget=BUDGET, **kw)


def prefill(mgr, slot, prompt, chunk=8, start=0):
    """What the engine does a chunk: advance, then (the device writes)."""
    for pos in range(start, len(prompt), chunk):
        mgr.window_advance(slot, pos, min(pos + chunk, len(prompt)))


def visible_pages(t_min):
    return set(range(max(0, t_min - WINDOW + 1) // PS, 10 ** 6))


def test_pages_go_back_as_a_row_advances_and_never_one_a_query_sees():
    mgr = manager()
    prompt = list(range(100, 160))                      # 60 tokens, 15 pages
    mgr.allocate_prefix(0, prompt, 60 + 20)
    assert len(mgr.tables[0]) == 20 and mgr.win_tables[0] == []
    held_most = 0
    for pos in range(0, 60, 8):
        new = mgr.window_advance(0, pos, min(pos + 8, 60))
        table = mgr.win_tables[0]
        # every page a query at `pos` or later sees is held, none behind is
        for i, pid in enumerate(table):
            assert (pid is not None) == (i in visible_pages(pos)), (pos, i)
        assert all(table[i] == pid for i, pid in new)
        held_most = max(held_most, mgr._win_held[0])
    assert held_most <= BUDGET
    assert mgr.window_pages_released == (56 - WINDOW + 1) // PS   # 10
    # decoding on: one step at a time
    for pos in range(60, 80):
        mgr.window_advance(0, pos, pos + 1)
        assert mgr._win_held[0] <= WINDOW // PS + 1
    free_before = len(mgr.win_free)
    mgr.free(0)
    assert len(mgr.win_free) + len(mgr._win_lru) == 23 and mgr._win_owed() == 0
    assert len(mgr.win_free) >= free_before


def test_a_released_page_serves_the_next_row_while_the_first_lives():
    mgr = manager(win=12)                    # 11 usable: one long row and a bit
    long_row = list(range(120))              # 30 pages of the full pool
    mgr.allocate_prefix(0, long_row, 120 + 4)
    prefill(mgr, 0, long_row)
    assert mgr._win_held[0] <= BUDGET
    # the same pages went round many times
    assert mgr.window_pages_released > 11


def test_admission_needs_both_pools():
    mgr = manager(full=64, win=12)           # window pool: 11 pages
    assert mgr.can_fit_prompt(list(range(100)), 120)        # budget 7 of 11
    mgr.allocate_prefix(0, list(range(100)), 120)
    # the full pool has 33 pages left, the window pool 4 unpromised: a row of
    # 5 pages fits neither way round, one of 4 does
    assert len(mgr.free_pages) == 63 - 30
    assert not mgr.can_fit_prompt(list(range(500, 520)), 20)
    assert not mgr.can_fit(20)
    assert mgr.can_fit_prompt(list(range(500, 516)), 16)
    with pytest.raises(MemoryError, match="window page pool"):
        mgr.allocate_prefix(1, list(range(500, 520)), 20)
    assert mgr.tables[1] == [] and len(mgr.free_pages) == 33   # nothing taken
    # and the other way round: the full pool is the one that is short
    mgr2 = manager(full=12, win=64)
    assert not mgr2.can_fit_prompt(list(range(100)), 100)
    assert mgr2.can_fit_prompt(list(range(40)), 44)


def _finished(mgr, slot, prompt, extra=4):
    """A request that prefills `prompt`, registers it and ends."""
    _, cached = mgr.allocate_prefix(slot, prompt, len(prompt) + extra)
    prefill(mgr, slot, prompt, start=cached)
    mgr.register_prefix(slot, prompt)
    mgr.free(slot)
    return cached


def test_a_follow_up_hits_at_the_finished_prompts_end_on_both_pools():
    mgr = manager()
    base = list(range(1000, 1042))                        # 42 tokens: 10 pages
    assert _finished(mgr, 0, base) == 0
    # the last window's pages stayed with their nodes: pages 6..9 of 10
    # (a query at 40 sees keys from 25: page 6), and the chunk's own before it
    assert mgr.window_stats()["window_pages_cached"] >= 4
    _, cached = mgr.allocate_prefix(1, base + [7] * 9, 51 + 4)
    assert cached == 40
    assert mgr.win_tables[1] == [None] * 6 + [
        n.win for n in mgr._walk(base)[6:10]]
    assert mgr.prefix_cut_by_window == mgr.prefix_lost_to_window == 0
    # borrowed pages are pinned: not evictable while the row lives
    assert not any(p in mgr._win_lru for p in mgr.win_tables[1] if p)
    mgr.free(1)


def test_a_match_is_cut_back_to_what_the_window_pool_still_holds_or_lost():
    mgr = manager()
    base = list(range(2000, 2042))
    _finished(mgr, 0, base)
    # a shorter prompt matches 5 pages of the chain, but the window pool holds
    # none of pages 1..4 any more (they went back while the first prefilled):
    # nothing of the match is whole: a miss
    _, cached = mgr.allocate_prefix(1, base[:22], 30)
    assert cached == 0 and mgr.prefix_lost_to_window == 1
    mgr.free(1)
    # that follow-up of the whole prompt would have hit at its end; with the
    # deepest node's window page gone no depth of it is whole any more (depth
    # 9 would need page 5, which went back while the first prefilled): lost
    mgr._win_unpublish(mgr._walk(base)[9])
    _, cached = mgr.allocate_prefix(2, base + [7] * 9, 55)
    assert cached == 0 and mgr.prefix_lost_to_window == 2
    mgr.free(2)
    # a prompt under the window keeps every page, so a shallower depth is
    # still whole when the deepest page goes: the match is CUT BACK to it
    short = list(range(3000, 3022))                       # 5 pages and 2
    _finished(mgr, 0, short)
    mgr._win_unpublish(mgr._walk(short)[4])
    _, cached = mgr.allocate_prefix(3, short + [7] * 9, 40)
    assert cached == 16 and mgr.prefix_cut_by_window == 1
    assert mgr.win_tables[3] == [n.win for n in mgr._walk(short)[:4]]
    mgr.free(3)


def test_both_pools_evict_each_by_its_own_need():
    mgr = manager(full=40, win=16)
    for i in range(12):                      # unrelated prompts of 5 pages
        _finished(mgr, 0, list(range(i * 100, i * 100 + 22)), extra=2)
    st = mgr.window_stats()
    assert st["window_pages_evicted"] > 0 and st["full_pages_evicted"] > 0
    assert st["window_pages_live"] == st["full_pages_live"] == 0
    # every page of either pool is free or cached, page 0 aside
    assert len(mgr.free_pages) + mgr.cached_pages == 39
    assert len(mgr.win_free) + st["window_pages_cached"] == 15
    # a full page's eviction takes its window page with it
    assert all(n.page is not None for n in mgr._win_node_of.values())


def test_num_pages_and_free_pages_describe_the_full_pool():
    mgr = manager(full=64, win=24)
    assert mgr.num_pages == 64 and len(mgr.free_pages) == 63
    mgr.allocate_prefix(0, list(range(30)), 40)
    assert len(mgr.free_pages) == 53 and mgr.pages_in_use == 10
    prefill(mgr, 0, list(range(30)))
    assert len(mgr.free_pages) == 53 and len(mgr.win_free) == 23 - 6


def test_a_manager_without_a_window_pool_is_what_it_was():
    mgr = PageManager(16, PS, 2, 8)
    assert mgr.win_num_pages == 0 and mgr._win_fits(10 ** 6)
    mgr.allocate_prefix(0, list(range(10)), 12)
    mgr.register_prefix(0, list(range(10)))
    mgr.free(0)
    assert mgr.win_tables[0] == [] and not mgr._win_node_of
