"""`PageManager` with two kinds of cache: pages of the full-attention layers
and snapshots of the linear layers' state. A prefix hit is worth only as far
as a node that holds a live snapshot."""

import pytest

from ray_tpu.serve.radix_cache import PageManager

PS = 4


def _mgr(snapshots=4, num_pages=64, slots=4, **kw):
    return PageManager(num_pages, PS, slots, 16, snapshots=snapshots, **kw)


def _serve(mgr, slot, prompt, extra=4):
    """One request's life as the engine drives it: allocate, save a snapshot
    at the prompt's last page boundary, publish, release."""
    _, cached = mgr.allocate_prefix(slot, prompt, len(prompt) + extra)
    boundary = (len(prompt) - 1) // PS * PS
    sid = None
    if cached < boundary:
        sid = mgr.reserve_snapshot(slot, boundary // PS)
    mgr.register_prefix(slot, prompt)
    resumed = mgr.resume_snapshot(slot)
    mgr.free(slot)
    return cached, sid, resumed


def test_a_hit_stops_at_the_deepest_snapshot():
    mgr = _mgr()
    first = list(range(18))             # 4 full pages, boundary at 16
    cached, sid, resumed = _serve(mgr, 0, first)
    assert (cached, resumed) == (0, -1) and sid is not None
    # the same 18 tokens and 10 more: 4 pages match and the snapshot is at 4
    cached, sid2, resumed = _serve(mgr, 1, first + list(range(100, 110)))
    assert cached == 16 and resumed == sid
    assert mgr.resume_gap_tokens == 0 and mgr.snapshot_hits == 1
    # that request saved its own snapshot deeper on the same path (28 tokens:
    # after 6 pages); the same 28 tokens asked again match 6 pages (one token
    # has to be left to prefill) and resume from it
    third = first + list(range(100, 110))
    cached, _, _ = _serve(mgr, 2, third)
    assert cached == 24
    stats = mgr.state_stats()
    assert stats["snapshots_saved"] == 2 and stats["snapshot_hits"] == 2


def test_pages_past_the_snapshot_are_prefilled_again():
    mgr = _mgr()
    prompt = list(range(16))            # exactly 4 pages: boundary at 12
    _serve(mgr, 0, prompt)
    assert mgr.snapshots_saved == 1
    # all 4 pages are published, the snapshot follows page 3: a longer prompt
    # matches 4 pages and resumes from 3, one page of gap
    cached, _, resumed = _serve(mgr, 1, prompt + [7] * 9)
    assert cached == 12 and resumed >= 0
    assert mgr.resume_gap_tokens == PS
    assert mgr.prefix_hit_tokens == 12    # what it did NOT prefill


def test_an_evicted_snapshot_shortens_the_hit():
    mgr = _mgr(snapshots=2)
    a, b, c = ([k] * 9 for k in (1, 2, 3))
    _serve(mgr, 0, a)
    _serve(mgr, 0, b)
    assert mgr.state_stats()["snapshots_live"] == 2
    _serve(mgr, 0, c)                   # the pool is full: a's goes (coldest)
    assert mgr.snapshots_evicted == 1
    cached, sid, _ = _serve(mgr, 0, a + [9] * 5)
    assert cached == 0                  # pages still match, no state: a miss
    assert sid is not None              # and it saves one again
    cached, _, _ = _serve(mgr, 0, c + [9] * 5)
    assert cached == 8


def test_a_snapshot_goes_with_its_page():
    mgr = _mgr(num_pages=8, slots=2)    # 7 usable pages
    a = [1] * 9                         # 3 pages allocated, 2 published
    _serve(mgr, 0, a, extra=0)
    assert mgr.state_stats()["snapshots_live"] == 1
    # a request that needs every page evicts a's chain, leaf first
    mgr.allocate(1, 7 * PS)
    assert mgr.state_stats()["snapshots_live"] == 0
    assert mgr.snapshots_evicted == 1
    mgr.free(1)
    cached, _, _ = _serve(mgr, 0, a + [2] * 3, extra=0)
    assert cached == 0


def test_a_deeper_save_turns_the_older_cold():
    mgr = _mgr(snapshots=3)
    base = [1] * 9
    _serve(mgr, 0, base)                        # snapshot A at page 2
    _serve(mgr, 0, [2] * 9)                     # B
    turn = base + [5] * 8
    _serve(mgr, 0, turn)                        # resumes from A, saves A'
    # the pool is full (A, B, A'); the next save evicts A, superseded on its
    # own path, not B, though A was used after B
    _serve(mgr, 0, [3] * 9)
    assert mgr.snapshots_evicted == 1
    assert _serve(mgr, 0, [2] * 9 + [4])[0] == 8        # B is still a hit
    assert _serve(mgr, 0, turn + [6] * 4)[0] == 16      # and so is A'


def test_counters_add_up_and_a_failed_request_gives_its_id_back():
    mgr = _mgr(snapshots=2)
    mgr.allocate_prefix(0, [1] * 9, 12)
    sid = mgr.reserve_snapshot(0, 2)
    assert sid is not None
    mgr.free(0)                         # ended before register_prefix
    assert sorted(mgr._snap_free) == [0, 1] and mgr.snapshots_saved == 0
    for k in range(5):
        _serve(mgr, 0, [10 + k] * 9)
    st = mgr.state_stats()
    assert st["snapshots_saved"] - st["snapshots_evicted"] == st["snapshots_live"] == 2
    assert len(mgr._snap_free) + st["snapshots_live"] == 2


def test_without_state_nothing_changes():
    mgr = _mgr(snapshots=0)
    prompt = list(range(18))
    assert _serve(mgr, 0, prompt)[:2] == (0, None)
    assert _serve(mgr, 1, prompt + [1, 2])[0] == 16      # pages alone are a hit
    assert mgr.reserve_snapshot(0, 3) is None
