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


# ------------------------------------------- a snapshot where a prompt leaves
def _serve_branching(mgr, slot, prompt, extra=4, release=True):
    """`_serve` as the engine drives it now: one further stop where the
    prompt leaves the tree past the snapshot it resumed from. Returns
    (cached, branch stop in pages, the branch id, the prompt's own id)."""
    _, cached = mgr.allocate_prefix(slot, prompt, len(prompt) + extra)
    boundary = (len(prompt) - 1) // PS * PS
    stop = mgr.branch_stop(slot)
    at_branch = own = None
    if cached < stop * PS < boundary:
        at_branch = mgr.reserve_snapshot(slot, stop, branch=True)
    if cached < boundary:
        own = mgr.reserve_snapshot(slot, boundary // PS)
    mgr.register_prefix(slot, prompt)
    if release:
        mgr.free(slot)
    return cached, stop, at_branch, own


def test_a_prompt_that_leaves_a_shared_prefix_saves_where_it_leaves():
    mgr = _mgr(snapshots=8)
    system = list(range(14))            # 3 whole pages and two tokens
    cached, stop, at_branch, own = _serve_branching(
        mgr, 0, system + [50] * 9)
    assert (cached, stop, at_branch) == (0, 0, None) and own is not None
    # the second shares 3 pages and no snapshot lies on them: a miss that
    # stops at depth 3 and saves there, and at its own end
    cached, stop, at_branch, own = _serve_branching(mgr, 1, system + [60] * 9)
    assert (cached, stop) == (0, 3)
    assert at_branch is not None and own is not None and own != at_branch
    st = mgr.state_stats()
    assert (st["branch_snapshots_saved"], st["snapshots_saved"]) == (1, 3)
    assert st["snapshot_hits"] == 0
    # the third resumes there: its gap is the two tokens of the system prompt
    # on the page it shares with nobody, under a page
    cached, stop, at_branch, own = _serve_branching(mgr, 2, system + [70] * 9)
    assert (cached, stop, at_branch) == (12, 0, None) and own is not None
    st = mgr.state_stats()
    assert (st["snapshot_hits"], st["branch_snapshot_hits"]) == (1, 1)
    assert st["resume_gap_tokens"] == 0 and len(system) - cached < PS
    assert mgr.resume_snapshot(2) == -1           # released: nothing is held


def test_a_match_that_ends_on_a_snapshot_saves_only_its_own():
    """A conversation's next turn: the pages it matches end on the last
    turn's snapshot, so there is no further stop and no branch is counted."""
    mgr = _mgr(snapshots=8)
    first = list(range(18))
    _serve_branching(mgr, 0, first)
    cached, stop, at_branch, own = _serve_branching(
        mgr, 1, first + list(range(100, 110)))
    assert (cached, stop, at_branch) == (16, 0, None) and own is not None
    st = mgr.state_stats()
    assert st["branch_snapshots_saved"] == st["branch_snapshot_hits"] == 0
    assert (st["snapshots_saved"], st["snapshot_hits"]) == (2, 1)


def test_two_prompts_that_race_to_one_node_leave_one_snapshot():
    mgr = _mgr(snapshots=8)
    system = list(range(14))
    _serve_branching(mgr, 0, system + [50] * 9)
    # both admitted before either reaches the stop: both are told to stop
    a, b = system + [60] * 9, system + [70] * 9
    mgr.allocate_prefix(1, a, len(a) + 4)
    mgr.allocate_prefix(2, b, len(b) + 4)
    assert mgr.branch_stop(1) == mgr.branch_stop(2) == 3
    first = mgr.reserve_snapshot(1, 3, branch=True)
    assert first is not None
    assert mgr.branch_stop(2) == 0                # the node has one now
    assert mgr.reserve_snapshot(2, 3, branch=True) is None
    for slot, prompt in ((1, a), (2, b)):
        assert mgr.reserve_snapshot(slot, 5) is not None
        mgr.register_prefix(slot, prompt)
        mgr.free(slot)
    st = mgr.state_stats()
    assert st["branch_snapshots_saved"] == 1
    assert st["snapshots_saved"] - st["snapshots_evicted"] == st["snapshots_live"] == 4
    assert len(mgr._snap_free) + st["snapshots_live"] == 8      # none leaked
    assert not mgr._pending_snap and not mgr._branch


def test_a_branch_snapshot_outlives_the_requests_behind_it_in_a_pool_of_two():
    """LRU under a pool of two: the requests that resume from the branch
    snapshot each save their own, which take each other's place; the branch
    snapshot, hit every time and never turned cold, stays."""
    mgr = _mgr(snapshots=2)
    system = list(range(14))
    _serve_branching(mgr, 0, system + [50] * 9)           # own (1 of 2)
    _serve_branching(mgr, 0, system + [60] * 9)           # branch + own: evicts
    assert mgr.state_stats()["snapshots_live"] == 2
    for k in range(5):
        cached, _, _, own = _serve_branching(mgr, 0, system + [70 + k] * 9)
        assert cached == 12 and own is not None
    st = mgr.state_stats()
    assert st["branch_snapshot_hits"] == 5 and st["branch_snapshots_saved"] == 1
    assert st["snapshots_saved"] - st["snapshots_evicted"] == 2


def test_a_branch_snapshot_goes_with_its_page_and_its_id_comes_back():
    mgr = _mgr(snapshots=4, num_pages=12, slots=2)
    system = list(range(14))
    _serve_branching(mgr, 0, system + [50] * 5, extra=0)
    _serve_branching(mgr, 0, system + [60] * 5, extra=0)
    assert mgr.state_stats()["branch_snapshots_saved"] == 1
    mgr.allocate(1, 11 * PS)            # every page: the chains are evicted
    st = mgr.state_stats()
    assert st["snapshots_live"] == 0 and len(mgr._snap_free) == 4
    mgr.free(1)
    assert _serve_branching(mgr, 0, system + [70] * 5, extra=0)[:2] == (0, 0)


def test_growing_sessions_read_as_they_did():
    """The agent-loop shape on the hybrid model's engine: every turn is the
    last prompt plus new text, so its match ends on the last turn's snapshot
    and the counters read what they read before the second depth: one save a
    turn, one hit a turn after the first, no gap, nothing at a branch. (A
    turn whose previous prompt is a whole number of pages long matches one
    page past that snapshot and saves a second one there: 1 turn in 8 at this
    page size, 1 in 64 at the cell's; none here.)"""
    import asyncio

    from ray_tpu.serve.llm import LLMConfig, LLMServer
    srv = LLMServer(LLMConfig(
        preset="solar_tiny", paged=True, prefix_cache=True, page_size=8,
        prefill_chunk=16, max_seq_len=128, num_pages=80, max_batch_slots=2,
        decode_chunk=4))
    try:
        assert srv.page_mgr.snapshots == 2 * 4        # four a slot, as before
        prompt = list(range(1, 22))
        for turn in range(4):
            asyncio.run(srv.generate(prompt, max_tokens=2))
            prompt = prompt + [100 + turn] * 10       # 21, 31 .. never k * 8
            assert len(prompt) % 8
        st = srv.stats()["state"]
        assert (st["snapshots_saved"], st["snapshot_hits"]) == (4, 3)
        assert st["branch_snapshots_saved"] == st["branch_snapshot_hits"] == 0
        assert st["resume_gap_tokens"] == 0
        assert (st["snapshot_copies"], st["restore_copies"]) == (4, 3)
    finally:
        srv.close()
