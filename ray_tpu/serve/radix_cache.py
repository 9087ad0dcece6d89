"""The serving engine's page manager: free list, per-slot block tables and
a radix prefix index over token-block KV pages, with a demotion tier.

Reference: vLLM's BlockSpaceManager for the allocator surface, sglang's
RadixAttention tree cache and vLLM's automatic prefix caching for the index.
The prefix index is an explicit radix tree over token blocks:

  * one trie node per FULL page of tokens; two prompts share nodes up to
    their exact divergence point, so sharing works at arbitrary branch
    points, not just one global prefix. Shared pages are read-only by
    construction (prefill skips them, decode writes land past the last full
    prompt page), i.e. the branch point is where copy-on-write happens: the
    diverging suffix gets fresh private pages while the common spine stays
    shared.
  * exact per-node accounting: each node counts its borrow hits, and the
    tree size / hit-token / evicted-page tallies are exported as registry
    metrics (`radix_*`, see util.metrics.radix_counters).
  * LRU-by-leaf eviction: only nodes with no RESIDENT children are eviction
    candidates, so the tree never creates unreachable descendants (a plain
    LRU can free page i while pages i+1.. stay cached yet unreachable: a
    prefix walk breaks at the hole).
  * demotion instead of discard: an evicted page's KV can be extracted into
    a sealed object-store segment (`demote_cb`); the node stays in the tree
    marked demoted, and a later request matching it restores the bytes into
    a fresh pool page (`restore_cb` — the serve engine wires this through
    `kv_transfer.KVPageStash`) instead of recomputing prefill. That is the
    HBM edge of the spill ladder: HBM page → shm segment → (object-store
    spill policy) → disk.

Two kinds of cache in the one manager. A model with linear-attention layers
keeps, beside the pages of its full-attention layers, one recurrent state a
slot, and the pages say nothing of it: a matched chain of pages is worth
only as far as a node that holds a SNAPSHOT of that state. With `snapshots`
> 0 the manager owns a pool of that many snapshot ids. A prompt's prefill
stops at its last page boundary, `reserve_snapshot` hands the engine an id
to copy the state into, and `register_prefix` records it on that page's
node; `allocate_prefix` then cuts a match back to the deepest node with a
live snapshot, and `resume_snapshot` tells the engine which id to copy into
the slot. Policy: a snapshot lives while its node's page is resident (it is
dropped when the page is evicted, and is not carried to the demotion tier;
since eviction is leaf first, the pages above an evicted snapshot can serve
no later prompt, so the engine wires no demotion hooks for such a model and
evicted pages are discarded), and when the pool is full the least recently
used goes first; a request that resumed from one and
saves a deeper one on the same path moves the older to the cold end, since
the next turn of that conversation will match the deeper one.

A second depth a prompt: where it LEAVES the tree. A prompt whose pages match
`d` deep while the deepest live snapshot on that path lies at `s < d` (many
requests behind one system prompt: each saved a snapshot at its own end, none
where they part) resumes from `s` as above, and `branch_stop` tells the
engine to stop once more, at `d`; `reserve_snapshot(branch=True)` there puts
the id on that node AT ONCE, if the page is still resident and no other
request got there first, so the next prompt that shares those pages resumes
from `d`. One further stop a prompt at most. A snapshot saved so is not
turned cold by the requests that resume from it (they all go their own way
past it: it is the one they share), and is counted apart.

Two kinds of PAGE in the one manager. A model with sliding-window layers
keeps their keys and values in a pool of its own (`window_pages` > 0), with
its own free list, table a slot, refcounts and LRU; everything above, and
`num_pages` / `free_pages`, describes the FULL pool, the one every token of a
row takes a page of. A row holds window pages only for positions a later
query of it can still see: `window_advance` gives back each page that has
fallen wholly behind `t_min - window` (to the LRU if a tree node holds it,
else to the free list) and takes the pages the row's next program writes.
Admission reserves a BUDGET of window pages a row (`window_budget` at most,
however long the row), a count and not pages, and admits only while the sum
of what rows may still take fits what is free or evictable, so a row's next
page is always there. A tree node may hold a window page beside its full one
(`_Node.win`): a finished prompt's last `window` tokens' pages stay with
their nodes. A match may be served at depth d only if the nodes of
(d - window, d] all hold one (there is no exact way to rebuild a sliding
layer's keys from the full layers'); else it is cut back to the deepest depth
that is whole, or lost. Window pages are never demoted: a manager with a
window pool takes no demotion hooks.

It knows page and snapshot ids and token ids only. What a page holds on the device, and
how pages are moved, is `ops/paged_attention.py`.
"""

import collections

from ray_tpu.util.tracing import PhaseTotals, phase

# Demoted nodes the tree keeps handles for (a second-chance tier, capped so
# the handle table cannot grow without bound). With `kv_transfer.py`'s
# DEMOTE_GROUP, STAGED_CAP_BYTES and STASH_BUDGET_BYTES, the constants of the
# demotion mechanism.
DEMOTE_CAP = 4096


def _count(name: str, value: float = 1.0):
    if value == 0:
        return
    try:
        from ray_tpu.util import metrics
        metrics.get_or_create(metrics.Counter, name,
                              "radix prefix cache tally").inc(value)
    except Exception:  # noqa: BLE001 - accounting never breaks serving
        pass


class _Node:
    """One full page of tokens in the radix tree."""

    __slots__ = ("tokens", "parent", "children", "page", "handle", "hits",
                 "snap", "win", "branch")

    def __init__(self, tokens, parent):
        self.tokens = tokens      # tuple of page_size token ids
        self.parent = parent
        self.children = {}        # tokens tuple -> _Node
        self.page = None          # pool page id while resident
        self.handle = None        # opaque demoted-KV handle (store segment)
        self.hits = 0
        self.snap = None          # snapshot id of the state after this page
        self.win = None           # window-pool page id of the same tokens
        self.branch = False       # `snap` was saved where a prompt left the tree

    @property
    def resident_children(self) -> int:
        return sum(1 for c in self.children.values() if c.page is not None)


class PageManager:
    """Host-side page allocator of the paged KV pool, and its prefix cache.

    Admission asks `can_fit(n_tokens)` / `can_fit_prompt`, `allocate(slot,
    n_tokens)` / `allocate_prefix` assign pool pages and return the table
    row, `extend(slot)` grabs the next page when a decode crosses a page
    boundary, `free(slot)` returns pages to the pool. `register_prefix`
    publishes a freshly-prefilled prompt's FULL pages as tree nodes;
    `allocate_prefix` links a new request's table to every already-published
    leading page (refcounted). Released published pages with refcount 0
    park in an LRU and are evicted back to the free list only under pool
    pressure, so repeated prompts keep hitting until memory actually runs
    out. With `prefix_cache=False` nothing is ever published: the tree stays
    empty and every request gets private pages.

    Hooks (all optional; without them the tree still branch-shares and
    evicts leaf-first, it just discards instead of demoting):

      demote_cb(page_id, node) -> handle | None
          Note that the page's KV is to be extracted from the device cache
          into durable storage (a sealed object-store segment) and return
          the handle a later `restore_cb` is given: valid at once, whether
          or not the bytes have left the device. Called at eviction time,
          once a page. None → discard.
      demote_flush_cb()
          Called once when an eviction pass ends, still inside
          `_evict_to_free`: no caller has yet been handed a freed page, so
          an extraction the hook DISPATCHES here runs on the device before
          any program that writes those pages. It need not wait for it.
          The owner reports a payload that never reached storage through
          `demotion_failed`.
      restore_cb(handle, page_id) -> bool
          Load a demoted page's KV back into the device cache at
          `page_id`. False/raise → the node is treated as a miss.
      drop_cb(handle)
          The demoted payload will never be restored (cap overflow or node
          removal); release its storage.
      phases
          The engine's `util.tracing.PhaseTotals`: one `_evict_to_free` call
          is its `evict` phase (the pass's demotion nests inside it).
    """

    def __init__(self, num_pages: int, page_size: int, batch_slots: int,
                 max_pages_per_seq: int, prefix_cache: bool = True,
                 demote_cb=None, restore_cb=None, drop_cb=None,
                 phases: PhaseTotals = None, demote_flush_cb=None,
                 snapshots: int = 0, window_pages: int = 0, window: int = 0,
                 window_budget: int = 0):
        if window_pages and (demote_cb or restore_cb):
            raise ValueError(
                "a page manager with a window pool takes no demotion hooks: "
                "a sliding layer's pages are not carried to the host stash "
                "or back (an evicted page is discarded)")
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_pages_per_seq = max_pages_per_seq
        # page 0 is reserved as the masked placeholder for unused table slots
        self.free_pages = list(range(num_pages - 1, 0, -1))
        self.tables = [[] for _ in range(batch_slots)]
        self._shared_count = [0] * batch_slots  # leading shared pages per slot
        self.prefix_cache_enabled = prefix_cache
        self._root = _Node((), None)
        self._node_of = {}  # page id -> its resident published _Node
        self._refs = {}     # published page id -> live borrower count
        # refcount-0 published pages (evictable), oldest first
        self._lru = collections.OrderedDict()
        self.demote_cb = demote_cb
        self.demote_flush_cb = demote_flush_cb
        self.restore_cb = restore_cb
        self.drop_cb = drop_cb
        self._demoted = collections.OrderedDict()  # demoted nodes, oldest first
        self.prefix_hit_tokens = 0
        self.prefix_query_tokens = 0
        self.prefix_nodes = 0          # live tree nodes (resident + demoted)
        self.evicted_pages = 0         # pages taken off the tree by the LRU
        self.demoted_pages = 0         # of those, extracted to the store
        self.restored_pages = 0        # demoted pages pulled back on a hit
        self.demote_failed = 0         # demotion raised: page discarded
        self.demote_last_error = None  # repr of the last such exception
        self._phases = phases or PhaseTotals("engine", ("evict",))
        # the state cache of a model with linear-attention layers
        self.snapshots = snapshots
        self._snap_free = list(range(snapshots - 1, -1, -1))
        self._snap_lru = collections.OrderedDict()  # nodes, coldest first
        self._pending_snap = {}    # slot -> (snapshot id, pages before it)
        self._resumed = {}         # slot -> node it resumed from (or None)
        # slot -> (node, depth in pages) where its prompt leaves the tree,
        # past the snapshot it resumed from
        self._branch = {}
        self.branch_snapshots_saved = 0
        self.branch_snapshot_hits = 0
        self.snapshots_saved = 0
        self.snapshots_evicted = 0
        self.snapshot_hits = 0
        self.resume_gap_tokens = 0
        # the window pool of a model with sliding layers (page 0 reserved)
        self.window = window              # keys a sliding query sees
        self.win_num_pages = window_pages
        self.window_budget = window_budget
        self.win_free = list(range(window_pages - 1, 0, -1))
        # a slot's window table by page index: a page id, or None where the
        # page was given back (or the row resumed past it)
        self.win_tables = [[] for _ in range(batch_slots)]
        self._win_budget = [0] * batch_slots   # pages the row may hold
        self._win_held = [0] * batch_slots     # pages it holds
        self._win_dead = [0] * batch_slots     # entries before this are gone
        self._win_node_of = {}   # published window page -> its node
        self._win_refs = {}      # published window page -> live borrowers
        self._win_lru = collections.OrderedDict()   # refcount 0, oldest first
        self.window_pages_released = 0
        self.window_pages_evicted = 0
        self.prefix_cut_by_window = 0
        self.prefix_lost_to_window = 0

    # ------------------------------------------------------------- tree walk
    def _page_tuples(self, prompt_ids) -> list:
        ps = self.page_size
        toks = [int(t) for t in prompt_ids]
        return [tuple(toks[i * ps:(i + 1) * ps])
                for i in range(len(toks) // ps)]

    def _walk(self, prompt_ids) -> list:
        """Maximal usable chain of tree nodes for this prompt: stops at the
        first page that is neither resident nor restorable (a hole breaks
        the chain — attention needs every leading page's KV)."""
        out = []
        cur = self._root
        restorable = self.restore_cb is not None
        for tokens in self._page_tuples(prompt_ids):
            node = cur.children.get(tokens)
            if node is None:
                break
            if node.page is None and (node.handle is None or not restorable):
                break
            out.append(node)
            cur = node
        return out

    def _set_nodes_gauge(self):
        try:
            from ray_tpu.util import metrics
            metrics.get_or_create(
                metrics.Gauge, "radix_prefix_nodes",
                "live radix prefix-tree nodes").set(self.prefix_nodes)
        except Exception:  # noqa: BLE001
            pass

    def _maybe_remove(self, node):
        """Unlink pageless, payloadless, childless nodes up the spine."""
        while (node is not None and node is not self._root
               and node.page is None and node.handle is None
               and not node.children):
            parent = node.parent
            parent.children.pop(node.tokens, None)
            node.parent = None
            self.prefix_nodes -= 1
            node = parent
        self._set_nodes_gauge()

    # -------------------------------------------------------------- eviction
    def _evict_node(self, pid: int, node):
        """Take `pid` off the tree: demote its KV if a demotion plane is
        wired (the node gets its handle NOW; the extraction is dispatched
        by `demote_flush_cb` when the pass ends, before the pool page can
        be recycled), else discard the node. The page returns to the free
        list either way."""
        self._lru.pop(pid, None)
        self._refs.pop(pid, None)
        self._node_of.pop(pid, None)
        node.page = None
        self._drop_snapshot(node)
        if node.win is not None:
            # a window page is borrowed only beside its node's full page, so
            # none is borrowed here
            self._win_unpublish(node)
        self.evicted_pages += 1
        _count("radix_evicted_pages")
        if node.handle is None and self.demote_cb is not None:
            try:
                node.handle = self.demote_cb(pid, node)
            except Exception as e:  # noqa: BLE001 - demotion is best-effort
                node.handle = None
                self.demote_failed += 1
                self.demote_last_error = repr(e)
        if node.handle is not None:
            self.demoted_pages += 1
            _count("radix_demoted_pages")
            self._demoted[node] = True
            self._demoted.move_to_end(node)
            while len(self._demoted) > DEMOTE_CAP:
                old, _ = self._demoted.popitem(last=False)
                self._drop_handle(old)
        else:
            self._maybe_remove(node)
        self.free_pages.append(pid)

    def demotion_failed(self, node, handle, error):
        """The payload behind a handle `demote_cb` returned never reached
        storage: the page counts as discarded, and the node stops
        advertising it (unless the handle is gone or replaced already)."""
        self.demote_failed += 1
        self.demote_last_error = repr(error)
        self.demoted_pages -= 1
        if node.handle is handle:
            node.handle = None
            self._demoted.pop(node, None)
            self._maybe_remove(node)

    def _drop_handle(self, node):
        handle, node.handle = node.handle, None
        if handle is not None and self.drop_cb is not None:
            try:
                self.drop_cb(handle)
            except Exception:  # noqa: BLE001
                pass
        self._maybe_remove(node)

    def _evict_to_free(self, need: int) -> bool:
        """Leaf-first LRU eviction until `need` pages are free: among
        refcount-0 published pages, only those whose node has no resident
        children are candidates, so an interior page is never freed while a
        descendant still depends on it for prefix matching."""
        with phase(self._phases, "evict"):
            while len(self.free_pages) < need and self._lru:
                victim = None
                for pid in self._lru:  # oldest first
                    if self._node_of[pid].resident_children == 0:
                        victim = pid
                        break
                if victim is None:
                    # borrowed pages pin their whole ancestor chain, so a
                    # resident leaf is always in the LRU before its ancestors;
                    # reaching here means the invariant broke — fail safe by
                    # taking the oldest (its node becomes a hole, walks stop
                    # there, nothing dangles).
                    victim = next(iter(self._lru))
                self._evict_node(victim, self._node_of[victim])
            if self.demote_flush_cb is not None:
                self.demote_flush_cb()
            return len(self.free_pages) >= need

    # ------------------------------------------------------------ snapshots
    def _drop_snapshot(self, node):
        if node.snap is not None:
            self._snap_free.append(node.snap)
            node.snap, node.branch = None, False
            self._snap_lru.pop(node, None)
            self.snapshots_evicted += 1

    def _usable(self, prompt_ids) -> tuple:
        """The chain a request for `prompt_ids` can start from, how many
        matched pages it has to give up, and the node those end on: the walk,
        less what would leave no token to prefill (the final chunk's logits
        come from running one), and for a model with state cut back to the
        deepest live snapshot, for one with a window pool to the deepest
        depth whose window is whole."""
        matched = self._walk(prompt_ids)
        while matched and len(matched) * self.page_size >= len(prompt_ids):
            matched.pop()
        if self.win_num_pages:
            deep = self._window_depth(matched)
            return matched[:deep], len(matched) - deep, None
        if not self.snapshots:
            return matched, 0, None
        deep = max((i + 1 for i, n in enumerate(matched)
                    if n.snap is not None), default=0)
        gap = len(matched) - deep
        return matched[:deep], gap, matched[-1] if gap else None

    # ---------------------------------------------------------- window pool
    def _window_first(self, position: int) -> int:
        """The first page a query at `position` still sees in a sliding
        layer."""
        return max(0, position - self.window + 1) // self.page_size

    def _window_depth(self, matched) -> int:
        """The deepest d <= len(matched) at which a request may resume: the
        nodes of (d - window, d] all hold a window page."""
        best = run = 0
        for i, node in enumerate(matched):
            run = run + 1 if node.win is not None else 0
            d = i + 1
            if run >= d - self._window_first(d * self.page_size):
                best = d
        return best

    def _win_owed(self) -> int:
        """Window pages the live rows may still take under their budgets."""
        return sum(self._win_budget) - sum(self._win_held)

    def _win_budget_for(self, n_tokens: int) -> int:
        return min(-(-n_tokens // self.page_size), self.window_budget)

    def _win_fits(self, n_tokens: int, borrowed=()) -> bool:
        """Whether a row of `n_tokens` that borrows the window pages of the
        nodes `borrowed` can be promised its budget."""
        if not self.win_num_pages:
            return True
        parked = sum(1 for n in borrowed if n.win in self._win_lru)
        return (self._win_owed() + self._win_budget_for(n_tokens)
                - len(borrowed)
                <= len(self.win_free) + len(self._win_lru) - parked)

    def _win_unpublish(self, node):
        pid, node.win = node.win, None
        self._win_node_of.pop(pid, None)
        self._win_lru.pop(pid, None)
        if not self._win_refs.pop(pid, 0):
            self.win_free.append(pid)
            self.window_pages_evicted += 1

    def _win_take_page(self) -> int:
        if not self.win_free:
            if not self._win_lru:
                raise MemoryError("window page pool exhausted")
            self._win_unpublish(self._win_node_of[next(iter(self._win_lru))])
        return self.win_free.pop()

    def _win_give_back(self, pid: int):
        """A row lets go of a window page: a published one parks in the LRU
        when its last borrower has, a private one is free."""
        if pid in self._win_node_of:
            self._win_refs[pid] -= 1
            if self._win_refs[pid] <= 0:
                self._win_refs[pid] = 0
                self._win_lru[pid] = True
        else:
            self.win_free.append(pid)

    def _win_open(self, slot: int, n_tokens: int, nodes=(), first: int = 0):
        """Start a slot's window table: `first` entries it will never see,
        then the borrowed pages of `nodes`."""
        for n in nodes:
            self._win_refs[n.win] = self._win_refs.get(n.win, 0) + 1
            self._win_lru.pop(n.win, None)
        self.win_tables[slot] = [None] * first + [n.win for n in nodes]
        self._win_budget[slot] = self._win_budget_for(n_tokens)
        self._win_held[slot] = len(nodes)
        self._win_dead[slot] = first

    def window_advance(self, slot: int, t_min: int, upto: int) -> list:
        """The slot's next program has its first query at position `t_min`
        or later and writes positions below `upto`: give back every window
        page that lies wholly before `t_min - window + 1` (no later query of
        the row sees it; programs already dispatched run before whatever is
        dispatched for the page's next owner), and take the pages up to
        `upto`. Returns the new (page index, page id) entries, for the
        device's table."""
        table = self.win_tables[slot]
        dead = min(self._window_first(t_min), len(table))
        for i in range(self._win_dead[slot], dead):
            if table[i] is not None:
                self._win_give_back(table[i])
                table[i] = None
                self._win_held[slot] -= 1
                self.window_pages_released += 1
        self._win_dead[slot] = max(self._win_dead[slot], dead)
        need = min(-(-upto // self.page_size), len(self.tables[slot]))
        new = []
        while len(table) < need:
            if self._win_held[slot] >= self._win_budget[slot]:
                raise MemoryError(
                    f"slot {slot} needs more than its budget of "
                    f"{self._win_budget[slot]} window pages")
            pid = self._win_take_page()
            new.append((len(table), pid))
            table.append(pid)
            self._win_held[slot] += 1
        return new

    def win_table_row(self, slot: int):
        row = [p or 0 for p in self.win_tables[slot]]
        return row + [0] * (self.max_pages_per_seq - len(row))

    def window_stats(self) -> dict:
        """The window pool's tallies and what the live rows hold now."""
        return {"window_pages_released": self.window_pages_released,
                "window_pages_evicted": self.window_pages_evicted,
                "full_pages_evicted": self.evicted_pages,
                "prefix_cut_by_window": self.prefix_cut_by_window,
                "prefix_lost_to_window": self.prefix_lost_to_window,
                "window_pages_live": sum(self._win_held),
                "full_pages_live": sum(len(t) for t in self.tables),
                "window_pages_free": len(self.win_free),
                "window_pages_cached": len(self._win_node_of)}

    def resume_snapshot(self, slot: int) -> int:
        """The snapshot the slot's last `allocate_prefix` resumed from, -1
        where it starts from nothing."""
        node = self._resumed.get(slot)
        return -1 if node is None or node.snap is None else node.snap

    def branch_stop(self, slot: int) -> int:
        """How many pages deep the slot's prompt leaves the tree, where that
        lies past the snapshot it resumed from and the node there holds none
        yet: the prefill stops there too. 0: no such stop."""
        node, depth = self._branch.get(slot, (None, 0))
        return depth if node is not None and node.snap is None else 0

    def reserve_snapshot(self, slot: int, n_pages: int, branch: bool = False):
        """An id for the state after the slot's first `n_pages` pages (the
        coldest snapshot gives way when none is free), to be recorded by
        `register_prefix`; None where the prefix cache keeps none. With
        `branch` the pages are the matched ones the slot's prompt leaves the
        tree after: the id goes onto that node now (it exists), unless its
        page has gone or another request's snapshot is there already."""
        if not (self.snapshots and self.prefix_cache_enabled and n_pages):
            return None
        node = None
        if branch:
            node, depth = self._branch.pop(slot, (None, 0))
            if (node is None or depth != n_pages or node.page is None
                    or node.snap is not None):
                return None
        if not self._snap_free:
            if not self._snap_lru:      # every id is some request's, unrecorded
                return None
            self._drop_snapshot(next(iter(self._snap_lru)))
        sid = self._snap_free.pop()
        if node is None:
            self._pending_snap[slot] = (sid, n_pages)
            return sid
        node.snap, node.branch = sid, True
        self._snap_lru[node] = True
        self.snapshots_saved += 1
        self.branch_snapshots_saved += 1
        return sid

    def _take_page(self):
        if not self.free_pages:
            self._evict_to_free(1)
        return self.free_pages.pop()

    def _available(self) -> int:
        return len(self.free_pages) + len(self._lru)

    # ------------------------------------------------------------- admission
    def can_fit(self, n_tokens: int) -> bool:
        need = -(-n_tokens // self.page_size)
        return (need <= self._available() and need <= self.max_pages_per_seq
                and self._win_fits(n_tokens))

    def can_fit_prompt(self, prompt_ids, n_tokens: int) -> bool:
        """can_fit that credits the prompt's cached-prefix pages: a
        prefix-hit request borrows those (refcounted, costing no free
        pages), so it must not stall in admission behind the full page
        bill while the pool is busy serving the very prompts it shares."""
        ps = self.page_size
        matched, _, _ = self._usable(prompt_ids)  # mirror allocate_prefix
        live = [n for n in matched if n.page is not None]
        need_total = -(-n_tokens // ps)
        # demoted matches restore into a fresh page each, so only LIVE
        # matches are free; LRU-parked live matches aren't evictable for
        # this request (borrowing pins them) — don't double-count them
        need_new = need_total - len(live)
        lru_matched = sum(1 for n in live if n.page in self._lru)
        return (need_new <= self._available() - lru_matched
                and need_total <= self.max_pages_per_seq
                and self._win_fits(n_tokens, self._win_borrowed(matched)))

    def _win_borrowed(self, matched) -> list:
        """The nodes whose window pages a request resuming after `matched`
        borrows: those of the last `window` tokens."""
        if not self.win_num_pages:
            return []
        return matched[self._window_first(len(matched) * self.page_size):]

    def allocate(self, slot: int, n_tokens: int):
        need = -(-n_tokens // self.page_size)
        if not self._win_fits(n_tokens):
            raise MemoryError("window page pool exhausted: the live rows' "
                              "budgets leave no room for another")
        if need > self._available():
            raise MemoryError(
                f"paged KV pool exhausted: need {need} pages, "
                f"{self._available()} free/evictable")
        if need > self.max_pages_per_seq:
            raise ValueError(
                f"sequence needs {need} pages > max_pages_per_seq "
                f"{self.max_pages_per_seq}")
        assert not self.tables[slot], f"slot {slot} already allocated"
        self.tables[slot] = [self._take_page() for _ in range(need)]
        self._shared_count[slot] = 0
        if self.win_num_pages:
            self._win_open(slot, n_tokens)
        return self.table_row(slot)

    def allocate_prefix(self, slot: int, prompt_ids, n_tokens: int):
        """Borrow the prompt's resident chain, restore its demoted links,
        and allocate fresh pages for the rest. Returns
        (table_row, cached_token_count); prefill starts at
        cached_token_count — restored pages are cached tokens too (that is
        the win: a disk/shm round trip instead of a prefill recompute). At
        least one prompt token is always left to prefill (the final-chunk
        logits come from running it)."""
        ps = self.page_size
        P = len(prompt_ids)
        self.prefix_query_tokens += P
        _count("radix_query_tokens", P)
        # a fully covered prompt still prefills its tail
        matched, gap, leaves_at = self._usable(prompt_ids)
        leaves_depth = len(matched) + gap
        need_total = -(-n_tokens // ps)
        if need_total > self.max_pages_per_seq:
            raise ValueError(
                f"sequence needs {need_total} pages > max_pages_per_seq "
                f"{self.max_pages_per_seq}")
        assert not self.tables[slot], f"slot {slot} already allocated"
        if self.win_num_pages:
            if gap and matched:       # pages matched that the window pool
                self.prefix_cut_by_window += 1     # could not serve whole
            elif gap:
                self.prefix_lost_to_window += 1
            if not self._win_fits(n_tokens, self._win_borrowed(matched)):
                raise MemoryError("window page pool exhausted: the live "
                                  "rows' budgets leave no room for another")
        # pin the live chain BEFORE any eviction: _evict_to_free scans the
        # LRU and could otherwise free the very pages being borrowed
        pinned = []
        for n in matched:
            if n.page is not None:
                self._refs[n.page] = self._refs.get(n.page, 0) + 1
                self._lru.pop(n.page, None)
                pinned.append(n)
        restored = []
        fresh = []
        try:
            # restore demoted links in chain order; the first failure
            # truncates the usable match there (later pinned nodes unpin)
            usable = []
            for n in matched:
                if n.page is not None:
                    usable.append(n)
                    continue
                if not self.free_pages and not self._evict_to_free(1):
                    break
                pid = self.free_pages.pop()
                ok = False
                try:
                    ok = bool(self.restore_cb(n.handle, pid))
                except Exception:  # noqa: BLE001 - restore is best-effort
                    ok = False
                if not ok:
                    self.free_pages.append(pid)
                    break
                n.page = pid
                self._node_of[pid] = n
                self._refs[pid] = 1
                self._demoted.pop(n, None)  # handle kept: re-demotion is free
                restored.append(n)
                usable.append(n)
            if len(usable) < len(matched):
                for n in matched[len(usable):]:
                    if n in pinned:
                        pinned.remove(n)
                        self._refs[n.page] -= 1
                        if self._refs[n.page] <= 0:
                            self._refs[n.page] = 0
                            self._lru[n.page] = True
                matched = usable
            need_fresh = need_total - len(matched)
            if need_fresh > len(self.free_pages) and not self._evict_to_free(
                    need_fresh):
                raise MemoryError(
                    f"paged KV pool exhausted: need {need_fresh} pages, "
                    f"{self._available()} free/evictable")
            fresh = [self.free_pages.pop() for _ in range(need_fresh)]
        except BaseException:
            for n in restored:  # un-restore: page back to pool, node demoted
                pid = n.page
                n.page = None
                self._node_of.pop(pid, None)
                self._refs.pop(pid, None)
                self._demoted[n] = True
                self.free_pages.append(pid)
            for n in pinned:  # rollback the borrow pins
                self._refs[n.page] -= 1
                if self._refs[n.page] <= 0:
                    self._refs[n.page] = 0
                    self._lru[n.page] = True
            raise
        self.tables[slot] = [n.page for n in matched] + fresh
        self._shared_count[slot] = len(matched)
        if self.win_num_pages:
            nodes = self._win_borrowed(matched)
            self._win_open(slot, n_tokens, nodes, len(matched) - len(nodes))
        for n in matched:
            n.hits += 1
        if restored:
            self.restored_pages += len(restored)
            _count("radix_restored_pages", len(restored))
        cached = len(matched) * ps
        if self.snapshots:
            # a restore that failed cut the chain short of its snapshot: the
            # pages stay borrowed (the prefill writes them what they hold),
            # the request starts from nothing
            node = matched[-1] if matched else None
            if node is not None and node.snap is None:
                node, cached = None, 0
            self._resumed[slot] = node
            if node is not None:
                self._snap_lru.move_to_end(node)
                self.snapshot_hits += 1
                self.branch_snapshot_hits += node.branch
                self.resume_gap_tokens += gap * ps
            if leaves_at is not None and leaves_at.page is not None:
                self._branch[slot] = (leaves_at, leaves_depth)
        self.prefix_hit_tokens += cached
        _count("radix_hit_tokens", cached)
        return self.table_row(slot), cached

    def register_prefix(self, slot: int, prompt_ids):
        """Publish the slot's freshly-prefilled FULL prompt pages as tree
        nodes. A node another request published first keeps its page (this
        slot's private copy returns to the pool at free()); a demoted node
        re-attaches — the fresh prefill recomputed exactly the KV its
        handle holds, so residency is restored for free."""
        if not self.prefix_cache_enabled:
            return
        table = self.tables[slot]
        cur = self._root
        for i, tokens in enumerate(self._page_tuples(prompt_ids)):
            if i >= len(table):
                break
            node = cur.children.get(tokens)
            if node is None:
                node = _Node(tokens, cur)
                cur.children[tokens] = node
                self.prefix_nodes += 1
            cur = node
            if self.win_num_pages:
                self._win_publish(slot, i, node, table[i])
            if node.page is not None:
                continue  # shared at admission or concurrently published
            if i < self._shared_count[slot]:
                continue  # borrowed chain: already accounted
            pid = table[i]
            node.page = pid
            self._node_of[pid] = node
            self._refs[pid] = self._refs.get(pid, 0) + 1
            self._demoted.pop(node, None)
        self._set_nodes_gauge()
        self._record_snapshot(slot, prompt_ids)

    def _win_publish(self, slot: int, i: int, node, full_page: int):
        """The slot's own window page of prompt page `i` goes onto the node,
        where the node holds none and its full page is (or is about to be)
        this slot's: whoever borrows the window page later borrows that full
        page with it, so the full page's refcount covers both."""
        wtable = self.win_tables[slot]
        if (node.win is not None or i >= len(wtable) or wtable[i] is None
                or wtable[i] in self._win_node_of
                or node.page not in (None, full_page)
                or (node.page is None and i < self._shared_count[slot])):
            return
        node.win = wtable[i]
        self._win_node_of[node.win] = node
        self._win_refs[node.win] = 1

    def _record_snapshot(self, slot: int, prompt_ids):
        """The snapshot the engine saved for this slot goes onto the node of
        the page it follows (unless another request's is there already, or
        the page did not stay resident); the one the slot resumed from, on
        the same path, turns cold."""
        sid, n_pages = self._pending_snap.pop(slot, (None, 0))
        if sid is None:
            return
        node = self._root
        for tokens in self._page_tuples(prompt_ids)[:n_pages]:
            node = node.children.get(tokens)
            if node is None:
                break
        if (node is None or node is self._root or node.page is None
                or node.snap is not None):
            self._snap_free.append(sid)
            return
        node.snap = sid
        self._snap_lru[node] = True
        self.snapshots_saved += 1
        old = self._resumed.get(slot)
        if (old is not None and old is not node and old in self._snap_lru
                and not old.branch):
            self._snap_lru.move_to_end(old, last=False)

    # ------------------------------------------------------- a slot's pages
    def extend(self, slot: int, new_len: int):
        """Ensure the slot's table covers new_len tokens; returns the row."""
        need = -(-new_len // self.page_size)
        while len(self.tables[slot]) < need:
            if not self.free_pages and not self._evict_to_free(1):
                raise MemoryError("paged KV pool exhausted during decode")
            if len(self.tables[slot]) >= self.max_pages_per_seq:
                raise ValueError("sequence exceeded max_pages_per_seq")
            self.tables[slot].append(self.free_pages.pop())
        return self.table_row(slot)

    def free(self, slot: int):
        """Return the slot's pages: published pages decref (parking in the
        LRU at zero, NOT the free list — a future prompt may hit them);
        private pages go straight back to the free list."""
        for pid in self.tables[slot]:
            if pid in self._node_of:
                self._refs[pid] -= 1
                if self._refs[pid] <= 0:
                    self._refs[pid] = 0
                    self._lru[pid] = True  # evictable, newest-last
            else:
                self.free_pages.append(pid)
        self.tables[slot] = []
        self._shared_count[slot] = 0
        for pid in self.win_tables[slot]:
            if pid is not None:
                self._win_give_back(pid)
        self.win_tables[slot] = []
        self._win_budget[slot] = self._win_held[slot] = 0
        self._win_dead[slot] = 0
        self._resumed.pop(slot, None)
        self._branch.pop(slot, None)
        sid, _ = self._pending_snap.pop(slot, (None, 0))
        if sid is not None:      # the request ended before it was recorded
            self._snap_free.append(sid)

    def table_row(self, slot: int):
        row = self.tables[slot]
        return row + [0] * (self.max_pages_per_seq - len(row))

    def table_slice(self, slot: int, start: int, n: int):
        """Page ids covering the slot's pages [start, start+n) — the PD
        KV-ship plane's extraction/install unit. Host-side bookkeeping is
        authoritative here, so suffix-delta shipping never pays a device
        sync just to learn which pool rows hold a chunk's pages."""
        row = self.tables[slot][start:start + n]
        if len(row) != n:
            raise IndexError(
                f"slot {slot} holds {len(self.tables[slot])} pages, "
                f"requested [{start}, {start + n})")
        return list(row)

    def shared_page_count(self, slot: int) -> int:
        """Leading pages this slot borrowed from the prefix cache (their
        KV is already resident — a PD decode replica needs only the
        suffix pages shipped, a PD prefill replica skips recomputing
        them)."""
        return self._shared_count[slot]

    # ------------------------------------------------------------ inspection
    @property
    def pages_in_use(self) -> int:
        return (self.num_pages - 1) - len(self.free_pages)

    @property
    def cached_pages(self) -> int:
        return len(self._node_of)

    def prefix_digest(self, max_bytes: int = None) -> dict:
        """Compact digest of this tree's hot prefixes for the affinity
        router (ISSUE 20): {chained page hash -> hits} over every node a
        request could actually borrow — resident pages AND demoted-but-
        restorable ones, so the digest is stable under LRU demotion to the
        stash (only a true discard drops an entry). Bounded to `max_bytes`
        packed (default RAY_TPU_PREFIX_DIGEST_BYTES=4096) by hottest-first
        truncation; children of a non-usable node are skipped because
        `_walk` stops at the hole anyway."""
        from ray_tpu.serve import prefix_digest as _pd
        if max_bytes is None:
            max_bytes = _pd.digest_max_bytes()
        restorable = self.restore_cb is not None
        cand = []
        stack = [(self._root, 0, 0)]
        while stack:
            node, chain, depth = stack.pop()
            for child in node.children.values():
                if child.page is None and (child.handle is None
                                           or not restorable):
                    continue  # hole: nothing below it is borrowable
                ch = _pd.chain_hash(chain, child.tokens)
                cand.append((ch, child.hits, depth + 1))
                stack.append((child, ch, depth + 1))
        return _pd.build(cand, self.page_size, max_bytes)

    def node_stats(self) -> dict:
        """Flat tree accounting for stats()/benchmarks."""
        return {"prefix_nodes": self.prefix_nodes,
                "resident_pages": len(self._node_of),
                "demoted_nodes": len(self._demoted),
                "evicted_pages": self.evicted_pages,
                "demoted_pages": self.demoted_pages,
                "restored_pages": self.restored_pages}

    def state_stats(self) -> dict:
        """The state cache's own tallies (a model with linear layers)."""
        return {"snapshots_saved": self.snapshots_saved,
                "snapshots_evicted": self.snapshots_evicted,
                "snapshot_hits": self.snapshot_hits,
                "branch_snapshots_saved": self.branch_snapshots_saved,
                "branch_snapshot_hits": self.branch_snapshot_hits,
                "resume_gap_tokens": self.resume_gap_tokens,
                "snapshots_live": len(self._snap_lru)}
