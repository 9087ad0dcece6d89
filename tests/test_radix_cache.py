"""Radix prefix index over KV pages (ISSUE 19 tentpole, half 1).

  * two prompts share tree nodes up to their exact divergence point (CoW:
    common spine borrowed read-only, diverging suffix gets private pages)
  * eviction is leaf-first and never frees a page a live slot borrows
  * demote→restore round-trips the page payload bit-identically, and a
    restored chain counts as cached tokens (prefill skips it)
  * KVPageStash (the serve-side shm→disk rung) round-trips k/v pages
    bit-identically through both tiers
  * prefix_cache=False is the same manager with nothing published
"""

import numpy as np

from ray_tpu.serve.radix_cache import PageManager

PS = 4  # tokens per page


def _mgr(num_pages=16, slots=8, max_seq=16, **hooks):
    return PageManager(num_pages, PS, slots, max_seq, True, **hooks)


def _prompt(*pages, tail=1):
    """Token ids for len(pages) full pages plus `tail` extra tokens."""
    toks = []
    for p in pages:
        toks.extend(range(p * 100, p * 100 + PS))
    toks.extend(range(9000, 9000 + tail))
    return toks


# ---------------------------------------------------------------- branching

def test_branch_prefixes_share_cow():
    """B borrows exactly A's common-prefix page; its diverging suffix gets
    fresh private pages (the branch point IS the copy-on-write point)."""
    m = _mgr()
    a = _prompt(1, 2)            # pages [1xx][2xx] + tail
    row, cached = m.allocate_prefix(0, a, len(a))
    assert cached == 0           # cold tree: everything prefills
    m.register_prefix(0, a)

    b = _prompt(1, 7)            # shares page [1xx], diverges at [7xx]
    row_b, cached_b = m.allocate_prefix(1, b, len(b))
    assert cached_b == PS        # one shared page of tokens
    assert m.tables[1][0] == m.tables[0][0]       # same physical page
    assert m.tables[1][1] != m.tables[0][1]       # private past the branch
    assert m.shared_page_count(1) == 1

    # exact full-prefix re-hit: all FULL pages cached, tail still prefills
    row_c, cached_c = m.allocate_prefix(2, a, len(a))
    assert cached_c == 2 * PS
    assert m.tables[2][:2] == m.tables[0][:2]
    assert m.prefix_hit_tokens == PS + 2 * PS


def test_register_then_free_keeps_pages_published():
    """free() decrefs borrowed pages back to the LRU, not the free list —
    the tree still resolves the prefix for the next request."""
    m = _mgr()
    a = _prompt(1, 2)
    m.allocate_prefix(0, a, len(a))
    m.register_prefix(0, a)
    m.free(0)
    _, cached = m.allocate_prefix(1, a, len(a))
    assert cached == 2 * PS


# ----------------------------------------------------------------- eviction

def test_eviction_spares_borrowed_pages():
    """Pool pressure evicts only unpinned published pages; a page a live
    slot borrows (and the whole chain under it) survives."""
    m = _mgr(num_pages=8)  # page 0 reserved -> 7 usable
    a = _prompt(1, 2)
    m.allocate_prefix(0, a, len(a))  # 3 pages
    m.register_prefix(0, a)

    b = _prompt(1, 2)                # borrows both published pages, 1 fresh
    _, cached = m.allocate_prefix(1, b, len(b))
    assert cached == 2 * PS
    m.free(0)  # slot 0's refs drop; pages stay pinned by slot 1's borrow

    c = _prompt(8, 9, tail=2 * PS)   # 4 pages: every remaining free page
    m.allocate_prefix(2, c, 4 * PS)
    # slot 1's borrowed chain is untouched and still resolves
    assert m.tables[1][0] is not None
    m.free(2)
    m.free(1)
    _, cached2 = m.allocate_prefix(3, a, len(a))
    assert cached2 == 2 * PS  # chain survived the pressure


def test_eviction_is_leaf_first():
    """The deepest refcount-0 node goes first; an interior page is never
    freed while a resident descendant still needs it for prefix walks."""
    m = _mgr(num_pages=8)
    a = _prompt(1, 2, 3)
    m.allocate_prefix(0, a, len(a))
    m.register_prefix(0, a)
    root_page, mid_page, leaf_page = m.tables[0][:3]
    m.free(0)

    assert m._evict_to_free(len(m.free_pages) + 1)
    assert leaf_page in m.free_pages          # leaf evicted...
    assert root_page in m._node_of and mid_page in m._node_of  # ...spine not

    # without a demotion plane the evicted leaf is a hole: the walk stops
    # at the last resident page
    _, cached = m.allocate_prefix(1, a, len(a))
    assert cached == 2 * PS


# ---------------------------------------------------------- demote / restore

def test_demote_restore_bit_identical():
    """An evicted page's payload is extracted at demotion and restored
    bit-identically into a fresh pool page on the next matching request —
    cached tokens include the restored pages."""
    device = {}          # fake device cache: page id -> payload
    stash = {}           # fake store: handle -> payload copy
    seq = iter(range(10 ** 6))

    def demote(pid, node):
        h = next(seq)
        stash[h] = device.pop(pid).copy()
        return h

    def restore(h, pid):
        device[pid] = stash[h].copy()
        return True

    def drop(h):
        stash.pop(h, None)

    m = _mgr(num_pages=8, demote_cb=demote, restore_cb=restore, drop_cb=drop)
    a = _prompt(1, 2)
    m.allocate_prefix(0, a, len(a))
    for pid in m.tables[0]:
        device[pid] = np.random.default_rng(pid).normal(size=(PS, 8))
    payloads = [device[pid].copy() for pid in m.tables[0][:2]]
    m.register_prefix(0, a)
    m.free(0)

    # drain the pool: 7 pages needed -> every published page demotes
    big = _prompt(8, 9, 10, tail=4 * PS)
    m.allocate_prefix(1, big, 7 * PS)
    assert m.demoted_pages >= 2
    m.free(1)

    _, cached = m.allocate_prefix(2, a, len(a))
    assert cached == 2 * PS               # restored pages ARE cached tokens
    assert m.restored_pages == 2
    for want, pid in zip(payloads, m.tables[2][:2]):
        np.testing.assert_array_equal(device[pid], want)


def test_restore_failure_truncates_match():
    """A failed restore degrades to a shorter cached prefix — the request
    prefills from the break instead of erroring."""
    def demote(pid, node):
        return "h"

    calls = []

    def restore(h, pid):
        calls.append(pid)
        return False

    m = _mgr(num_pages=8, demote_cb=demote, restore_cb=restore)
    a = _prompt(1, 2)
    m.allocate_prefix(0, a, len(a))
    m.register_prefix(0, a)
    m.free(0)
    big = _prompt(8, 9, 10, tail=4 * PS)
    m.allocate_prefix(1, big, 7 * PS)
    m.free(1)

    _, cached = m.allocate_prefix(2, a, len(a))
    assert calls and cached == 0          # restore refused -> full prefill
    m.register_prefix(2, a)               # fresh prefill re-publishes
    m.free(2)
    _, cached2 = m.allocate_prefix(3, a, len(a))
    assert cached2 == 2 * PS


# -------------------------------------------------------------- KVPageStash

def test_kv_page_stash_roundtrip_two_tiers(monkeypatch):
    """put → (budget pressure: shm → disk) → get promotes and round-trips
    bit-identically; tier gauges track both rungs."""
    monkeypatch.delenv("RAY_TPU_ARENA", raising=False)
    from ray_tpu.serve.kv_transfer import KVPageStash

    one_page = 2 * 2 * 3 * PS * 8 * 4    # k+v, [L=2,Kh=3,ps,D=8] float32
    stash = KVPageStash(budget_bytes=one_page + 16)  # fits ONE page in shm
    try:
        rng = np.random.default_rng(0)
        k1 = rng.normal(size=(2, 3, PS, 8)).astype(np.float32)
        v1 = rng.normal(size=(2, 3, PS, 8)).astype(np.float32)
        layout = [{"shape": list(k1.shape), "dtype": "float32"}] * 2
        h1 = stash.new_handle(layout)
        stash.put([h1], k1[None], v1[None])
        k2, v2 = k1 * 2, v1 * 2
        h2 = stash.new_handle(layout)
        # budget: h1 spills to disk, on the stash's own thread
        assert stash.put([h2], k2[None], v2[None]).result(60) == [None]
        ts = stash.tier_stats()
        assert ts["disk_objects"] == 1 and ts["shm_objects"] == 1, ts

        gk, gv = stash.get(h1)           # disk -> shm promotion
        np.testing.assert_array_equal(gk, k1)
        np.testing.assert_array_equal(gv, v1)
        gk2, gv2 = stash.get(h2)
        np.testing.assert_array_equal(gk2, k2)
        np.testing.assert_array_equal(gv2, v2)
        stash.drop(h1)
        stash.drop(h2)
    finally:
        stash.close()


# ------------------------------------------------------ prefix cache off

def test_prefix_cache_off_is_the_same_manager_with_nothing_published():
    m = PageManager(16, PS, 8, 16, prefix_cache=False)
    assert type(m) is type(_mgr())
    a = _prompt(1, 2, 3)
    for slot in (0, 1):
        assert m.can_fit_prompt(a, len(a))
        _, cached = m.allocate_prefix(slot, a, len(a))
        assert cached == 0
        m.register_prefix(slot, a)
        assert m.shared_page_count(slot) == 0
    assert not set(m.tables[0]) & set(m.tables[1])   # nothing shared
    assert m.cached_pages == 0 and m.prefix_nodes == 0
    assert m.prefix_digest() == _mgr().prefix_digest()    # an empty tree's
    m.free(0)
    m.free(1)
    assert m.pages_in_use == 0 and len(m.free_pages) == 15
    assert not m._lru and not m._refs and not m._node_of
