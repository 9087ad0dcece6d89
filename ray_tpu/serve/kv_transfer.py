"""KV pages that leave the page pool: the prefill/decode shipment plane and
the demotion tier.

A prefill replica hands a prompt's KV to a decode replica as a STREAMING
data plane over the object store; no RPC frame carries KV bytes:

  * the PREFILL side seals extracted KV pages into per-object shm
    segments (``StoreClient.create_writable`` → fill → seal, plasma
    Create/Seal semantics) and puts only segment *metadata* in the RPC
    frame (oid, byte count, page range — a few hundred bytes);
  * the DECODE side pulls each segment the cheapest way available:
    same-host it attaches the segment by name (zero copies end to end —
    the install scatter reads straight out of the prefill replica's shm
    pages); cross-host it rides ``node_agent.parallel_fetch``'s
    4-stream ranged transfer into a local segment; and when neither
    plane is reachable it falls back to a raw-bytes RPC fetch.

Segments are published per prefill CHUNK, so the decode pull of chunk i
overlaps the prefill compute of chunk i+1 — the serving-side analog of
the r8 prefetch/execute overlap.

Pages the radix tree evicts go the same way out of the pool and come back
by it: `DemotionTier` owns them from the gather on the device to the
`KVPageStash` segment (shm, then disk) and back into a pool page.

Naming: ``object_store.seg_name`` keeps only the oid's last 16 chars,
so ship oids are exactly 16 chars — an 8-hex per-process tag, a 4-hex
ship counter, a 3-hex segment index, and one role suffix. The storage
segment (``…s``) and the wire id served to remote pullers (``…w``)
differ in that suffix so a same-host puller forced onto the remote path
can never clobber the writer's live segment when ``parallel_fetch``
lands the copy under the wire id.
"""

import asyncio
import collections
import concurrent.futures
import itertools
import os
import socket as _socket
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu._private.object_store import StoreClient, seg_name
from ray_tpu.util import metrics as _metrics
from ray_tpu.util.tracing import PhaseTotals, phase

_proc_tag = os.urandom(4).hex()          # 8 chars, fresh per process
_ship_counter = itertools.count(1)


# shm a stash holds in demoted KV pages before it spills its oldest segments
# to the disk tier (what a KVPageStash built without `budget_bytes` gets)
STASH_BUDGET_BYTES = 256 << 20

# Demotion of evicted prefix pages. One gather program whatever the pass
# size: its index vector always has DEMOTE_GROUP entries, padded with the
# reserved placeholder page 0, and a longer pass calls it again. Gathered
# pages are staged (on the device and, once copied, on the host) until the
# stash's thread has sealed them: over STAGED_CAP_BYTES (or the deployment's
# own `LLMConfig.staged_cap_bytes`) the loop waits for the oldest hand-off.
# Restored pages go back in by the same group size. Constants, in pages and
# bytes, so every page size is covered by one path; the mechanism's other two
# are `radix_cache.DEMOTE_CAP` and STASH_BUDGET_BYTES.
DEMOTE_GROUP = 8
STAGED_CAP_BYTES = 128 << 20
# what a DemotionTier counts, under the names stats()["decode"] gives them
TIER_COUNTERS = {"demote_bytes": 0, "demote_passes": 0, "demote_wait_s": 0.0,
                 "demote_inflight_max_bytes": 0, "restored_in_flight": 0}


def new_ship_id() -> str:
    return f"{_proc_tag}{next(_ship_counter) & 0xFFFF:04x}"


def _seg_base(ship_id: str, seg_index: int) -> str:
    return f"{ship_id}{seg_index & 0xFFF:03x}"


def storage_oid(ship_id: str, seg_index: int) -> str:
    return _seg_base(ship_id, seg_index) + "s"


def wire_oid(ship_id: str, seg_index: int) -> str:
    return _seg_base(ship_id, seg_index) + "w"


def _counter(name: str, desc: str) -> "_metrics.Counter":
    return _metrics.get_or_create(_metrics.Counter, name, desc)


def _np_dtype(name: str) -> np.dtype:
    """np.dtype by name, including the ml_dtypes extension types (the KV
    pools are usually bfloat16, which np.dtype() can't resolve by string)."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def _as_bytes(arr: np.ndarray) -> memoryview:
    """Flat byte view of a C-contiguous array; works for extension dtypes
    (bfloat16) that memoryview() itself refuses to export."""
    return memoryview(arr.view(np.uint8)).cast("B")


class ShipWriter:
    """Prefill-side segment publisher over a pershm StoreClient.

    pershm is forced regardless of the session arena: decode attaches
    segments cross-process by NAME, and slab offsets are meaningless
    outside the owning process's arena mapping."""

    def __init__(self):
        self.store = StoreClient(backend="pershm")
        self._sizes: Dict[str, int] = {}       # storage oid -> nbytes
        self._ship_oids: Dict[str, List[str]] = {}  # ship -> storage oids

    def publish(self, ship_id: str, seg_index: int, blocks,
                page_start: int, n_pages: int) -> Dict[str, Any]:
        """Seal one segment: `blocks` is one array of every per-page pool of
        the cache, each in its pool's own form with the segment's `n_pages`
        pages where the pool has its pages ([L,Kh,n,ps,D] for the dense
        layout), written C-contiguous one after another. Returns the
        segment's wire metadata."""
        blocks = [np.ascontiguousarray(b) for b in blocks]
        nbytes = sum(b.nbytes for b in blocks)
        oid = storage_oid(ship_id, seg_index)
        handle = self.store.create_writable(oid, nbytes)
        try:
            _write_blocks(handle.view, blocks)
        except BaseException:
            handle.abort()
            raise
        handle.seal()
        self._sizes[oid] = nbytes
        self._ship_oids.setdefault(ship_id, []).append(oid)
        _counter("kv_ship_bytes", "KV bytes sealed for PD shipment").inc(
            nbytes)
        _counter("kv_ship_pages", "KV pages sealed for PD shipment").inc(
            n_pages)
        _counter("kv_ship_segments", "KV shipment segments sealed").inc()
        return {"seg": seg_index, "oid": oid,
                "wire": wire_oid(ship_id, seg_index), "nbytes": nbytes,
                "page_start": int(page_start), "n_pages": int(n_pages)}

    def read_segment(self, oid: str) -> bytes:
        """Raw bytes for the RPC fetch fallback (the one path that puts
        KV bytes back in an RPC frame — used only when both the shm
        attach and the data-server pull are unavailable)."""
        if oid not in self._sizes:
            raise KeyError(f"unknown kv segment {oid}")
        _counter("kv_ship_rpc_fallback_bytes",
                 "KV bytes served via the RPC fetch fallback").inc(
                     self._sizes[oid])
        return self.store.read_raw(oid)

    def size_of(self, oid: str) -> Optional[int]:
        return self._sizes.get(oid)

    def drop_ship(self, ship_id: str) -> None:
        """Free every segment of one shipment (decode finished installing,
        or the request failed)."""
        for oid in self._ship_oids.pop(ship_id, []):
            self._sizes.pop(oid, None)
            try:
                self.store.delete_segment(oid)
            except Exception:  # noqa: BLE001 - already gone is fine
                pass

    def close(self) -> None:
        for ship_id in list(self._ship_oids):
            self.drop_ship(ship_id)


def _write_blocks(view, blocks) -> None:
    """`blocks` C-contiguous one after another into a segment's `view`."""
    at = 0
    for block in blocks:
        block = np.ascontiguousarray(block)
        view[at:at + block.nbytes] = _as_bytes(block)
        at += block.nbytes


def _read_blocks(buf, specs) -> Tuple[Tuple[np.ndarray, ...], int]:
    """The arrays `_write_blocks` laid into `buf`, zero-copy, from their
    (shape, dtype) `specs` in order; and the bytes they take."""
    out, at = [], 0
    for shape, dtype in specs:
        n = int(np.prod(shape))
        out.append(np.frombuffer(buf, dtype=dtype, count=n,
                                 offset=at).reshape(shape))
        at += n * dtype.itemsize
    return tuple(out), at


def _blocks_of(handle: Dict[str, Any]) -> List[Tuple[tuple, np.dtype]]:
    """(shape, dtype) of each block a stash handle's segment holds, in
    order."""
    return [(tuple(b["shape"]), _np_dtype(b["dtype"]))
            for b in handle["blocks"]]


class KVPageStash:
    """Demotion tier for radix prefix pages (ISSUE 19 tiering, the HBM
    edge of the spill ladder).

    When the radix tree LRU-evicts a cold prefix page, its KV is sealed
    into a pershm store segment here (same Create→fill→Seal plane the PD
    shipment uses) instead of being discarded; a later request matching
    the node restores the bytes into a fresh HBM page rather than
    recomputing prefill. Restore walks the same rung order as ShipReader's
    pull ladder: same-host shm attach first, then the DISK tier — under
    shm pressure (`STASH_BUDGET_BYTES`) the stash demotes its oldest
    segments with ``StoreClient.spill`` (atomic temp+rename files), and a
    hit on a disk-resident handle promotes it back through
    ``StoreClient.restore``. Per-tier occupancy is exported on the
    ``store_tier_*`` gauges under the ``owner=kv_stash`` series.

    The store and both tier tables belong to ONE worker thread: `put`,
    `get`, `drop` and `close` all run there, in the order they were
    called, so nothing here takes a lock. `put` returns at once (the
    engine loop hands over gathered pages whose transfer to the host is
    still in flight and goes on); `get` and `close` wait for their turn,
    so everything submitted before them has run. The caller keeps the
    arrays it gave `put` until the returned future is done: until then
    they are the only copy.

    Handles are content-immutable (a prefix page's tokens fully determine
    its KV), so a handle stays valid across any number of demote/restore
    round trips, and is valid from `new_handle` on."""

    def __init__(self, budget_bytes: int = STASH_BUDGET_BYTES):
        self.store = StoreClient(backend="pershm")
        self._seq = itertools.count(1)
        self._shm: "collections.OrderedDict[str, int]" = \
            collections.OrderedDict()          # oid -> nbytes, oldest first
        self._disk: Dict[str, Tuple[str, int]] = {}   # oid -> (path, nbytes)
        self.budget = budget_bytes
        self.shm_bytes = 0
        self.disk_bytes = 0
        self.spilled_pages = 0     # segments the budget moved to disk, ever
        # the worker's busy seconds, and its `stash.put` span in a
        # profiler trace (a PhaseTotals of its own: one runs on one thread)
        self.phases = PhaseTotals("stash", ("put",))
        self._worker = concurrent.futures.ThreadPoolExecutor(
            1, thread_name_prefix="kv-stash")

    def _gauge(self):
        try:
            tags = {"owner": "kv_stash"}
            g = lambda name, desc: _metrics.get_or_create(  # noqa: E731
                _metrics.Gauge, name, desc, tag_keys=("owner",))
            g("store_tier_shm_bytes",
              "bytes resident in the shm tier").set(self.shm_bytes, tags)
            g("store_tier_disk_bytes",
              "bytes demoted to the disk tier").set(self.disk_bytes, tags)
            g("store_tier_shm_objects",
              "objects resident in the shm tier").set(len(self._shm), tags)
            g("store_tier_disk_objects",
              "objects demoted to the disk tier").set(len(self._disk), tags)
        except Exception:  # noqa: BLE001 - accounting never breaks serving
            pass

    # -- the caller's side: each submits to the worker ----------------------
    def new_handle(self, layout) -> Dict[str, Any]:
        """The restore handle of a page that `put` will be given later.
        `layout` describes the page (`ops.paged_attention.page_layout`: one
        entry a block with its `shape` and `dtype` name); the handle records
        it."""
        handle = {"oid": f"kvd{_proc_tag}{next(self._seq):08x}",
                  "blocks": layout}
        handle["nbytes"] = sum(int(np.prod(sh)) * dt.itemsize
                               for sh, dt in _blocks_of(handle))
        return handle

    def put(self, handles: List[Dict[str, Any]], *pages
            ) -> concurrent.futures.Future:
        """Seal page i of each of `pages` (one array a block of the
        handles' layout, each [G, *its block shape], numpy or device arrays
        whose copy to the host may still be in flight) under `handles[i]`;
        rows past `len(handles)` are padding. Returns at once: the result is
        one entry a handle, None or the exception that kept that page out of
        the stash."""
        return self._worker.submit(self._put, handles, pages)

    def get(self, handle: Dict[str, Any]) -> Tuple[np.ndarray, ...]:
        """Restore one page's blocks, in its layout's order, promoting a
        disk-resident segment back to shm first. Byte-exact:
        the arrays round-trip untouched."""
        return self._worker.submit(self._get, handle).result()

    def drop(self, handle: Dict[str, Any]) -> None:
        """The handle will never be restored; free its tier residency
        (after the put that fills it, if that is still queued)."""
        self._worker.submit(self._drop, handle)

    def close(self) -> None:
        """Runs what is queued, then gives back every segment and spill
        file and ends the worker thread."""
        try:
            done = self._worker.submit(self._drop_all)
        except RuntimeError:    # closed before
            return
        try:
            done.result()
        finally:
            self._worker.shutdown()

    def tier_stats(self) -> Dict[str, int]:
        return {"shm_objects": len(self._shm), "shm_bytes": self.shm_bytes,
                "disk_objects": len(self._disk),
                "disk_bytes": self.disk_bytes}

    # -- the worker's side ----------------------------------------------------
    def _put(self, handles, pages) -> List[Optional[Exception]]:
        with phase(self.phases, "put"):
            pages = [np.asarray(p) for p in pages]   # waits for the transfer
            errors: List[Optional[Exception]] = []
            for i, handle in enumerate(handles):
                try:
                    self._seal(handle, *(p[i] for p in pages))
                    errors.append(None)
                except Exception as e:  # noqa: BLE001 - the caller counts it
                    errors.append(e)
            self._gauge()
            return errors

    def _seal(self, handle, *blocks) -> None:
        """One evicted page's blocks (each C-contiguous) one after another
        into a sealed segment."""
        oid, nbytes = handle["oid"], handle["nbytes"]
        if sum(b.nbytes for b in blocks) != nbytes:
            raise ValueError(f"page {oid}: {len(blocks)} blocks of "
                             f"{sum(b.nbytes for b in blocks)} bytes, the "
                             f"handle records {nbytes}")
        buf = self.store.create_writable(oid, nbytes)
        try:
            _write_blocks(buf.view, blocks)
        except BaseException:
            buf.abort()
            raise
        buf.seal()
        self._shm[oid] = nbytes
        self.shm_bytes += nbytes
        self._enforce_budget()

    def _enforce_budget(self):
        """shm → disk rung: spill oldest stash segments past the budget."""
        while self.shm_bytes > self.budget and self._shm:
            oid, nbytes = self._shm.popitem(last=False)
            self.shm_bytes -= nbytes
            try:
                path = self.store.spill(oid)
            except Exception:  # noqa: BLE001 - segment vanished → forget it
                continue
            self._disk[oid] = (path, nbytes)
            self.disk_bytes += nbytes
            self.spilled_pages += 1

    def _get(self, handle) -> Tuple[np.ndarray, ...]:
        oid = handle["oid"]
        if oid in self._disk:
            path, nbytes = self._disk.pop(oid)
            self.disk_bytes -= nbytes
            self.store.restore(oid, path)
            self._shm[oid] = nbytes
            self.shm_bytes += nbytes
            self._enforce_budget()
        elif oid in self._shm:
            self._shm.move_to_end(oid)  # hot again
        blob = self.store.read_raw(oid)
        self._gauge()
        return _read_blocks(blob, _blocks_of(handle))[0]

    def _drop(self, handle) -> None:
        oid = handle["oid"]
        if oid in self._shm:
            self.shm_bytes -= self._shm.pop(oid)
            try:
                self.store.delete_segment(oid)
            except Exception:  # noqa: BLE001
                pass
        elif oid in self._disk:
            path, nbytes = self._disk.pop(oid)
            self.disk_bytes -= nbytes
            try:
                os.remove(path)
            except OSError:
                pass
        self._gauge()

    def _drop_all(self) -> None:
        for oid in list(self._shm) + list(self._disk):
            self._drop({"oid": oid})


class DemotionTier:
    """Owner of every prefix page between the pool and the stash: the pass
    being evicted, the gathered groups whose copy to the host or whose seal
    is still in flight, the restores not yet landed, the two programs that
    move whole pages (both always DEMOTE_GROUP page ids), the stash itself.

    `hooks()` is what a `radix_cache.PageManager` is built with; `failed` is
    the manager's `demotion_failed`, the one call back. The cache is never
    kept: `read_cache()` gives the newest one when a pass is gathered (the
    manager calls `demote_pass` from inside an allocation), `flush_restores`
    and `warm` take it and return the donated result. Everything but
    `staged_bytes` (a plain read) belongs to the engine loop's thread.

    Build it, then `cache = tier.warm(cache)` before the first eviction:
    both programs compile there, so nothing compiles once a replica serves.
    `phases` must have `demote`, `demote_stash` and `restore`."""

    def __init__(self, read_cache, phases: PhaseTotals, failed,
                 staged_cap_bytes: Optional[int] = None):
        self.stash = KVPageStash()
        self.staged_cap_bytes = staged_cap_bytes or STAGED_CAP_BYTES
        self._read_cache = read_cache
        self._phases = phases
        self._failed = failed
        # the pass being evicted [(page id, node, handle)]; hand-offs the
        # stash's thread has not been seen to finish, oldest first [(future,
        # pages, nbytes)]; the staged copy of every page in them, oid -> (its
        # group of each per-page array, row); restores fetched and not yet
        # in the pool [(page id, blocks)]
        self._evicting = []
        self._handoffs = collections.deque()
        self._staged = {}
        self.staged_bytes = 0
        self._pending_restores = []
        self._layout = self._gather = self._scatter = None
        self.counters = dict(TIER_COUNTERS)

    def warm(self, cache):
        """Take `cache`'s page layout and compile both programs against it
        (the restore writes zeros to the placeholder page 0). Returns the
        cache the restore was donated."""
        import jax

        from ray_tpu.ops.paged_attention import (gather_pages, page_layout,
                                                 scatter_pages)
        self._layout = page_layout(cache)
        # pages out and back in by groups of DEMOTE_GROUP ids, page-major,
        # the restore into the donated cache
        self._gather = jax.jit(gather_pages)
        self._scatter = jax.jit(scatter_pages, donate_argnums=(0,))
        idx = np.zeros((DEMOTE_GROUP,), np.int32)
        self._gather(cache, idx)
        return self._scatter(cache, idx, tuple(
            np.zeros((DEMOTE_GROUP, *block["shape"]), pool.dtype)
            for block, pool in zip(self._layout, cache.pools())))

    def hooks(self) -> Dict[str, Any]:
        return dict(demote_cb=self.demote_page,
                    demote_flush_cb=self.demote_pass,
                    restore_cb=self.restore_page, drop_cb=self.drop_page)

    def demote_page(self, pid: int, node) -> Dict[str, Any]:
        """radix demote_cb: note page `pid` for this pass's gather and give
        its node the handle its blocks (one of every per-page pool: k, v,
        and an indexer's keys where the cache has them) will be stashed
        under. Nothing leaves the device here."""
        handle = self.stash.new_handle(self._layout)
        self._evicting.append((pid, node, handle))
        return handle

    def demote_pass(self) -> None:
        """radix demote_flush_cb, at the end of an eviction pass: DISPATCH
        the gather of the pass's pages out of the pool, start their copy to
        the host and hand them to the stash's thread, which waits for the
        copy, seals and spills. The loop waits for none of it: the gather
        is on the device stream before the admitting request's prefill and
        every later decode chunk, so the pool pages are free to be written
        at once. Whatever raises here discards the pages it had not handed
        over and is counted; serving goes on."""
        pages, self._evicting = self._evicting, []
        if not pages:
            return
        with phase(self._phases, "demote"):
            self.reap()
            handed = 0
            try:
                cache = self._read_cache()
                groups = []
                for i in range(0, len(pages), DEMOTE_GROUP):
                    part = pages[i:i + DEMOTE_GROUP]
                    idx = np.zeros((DEMOTE_GROUP,), np.int32)
                    idx[:len(part)] = [pid for pid, _, _ in part]
                    blocks = self._gather(cache, idx)
                    for block in blocks:
                        block.copy_to_host_async()
                    groups.append((part, blocks))
                self.counters["demote_passes"] += 1
                with phase(self._phases, "demote_stash"):
                    for part, blocks in groups:
                        self._hand_off(part, blocks)
                        handed += len(part)
            except Exception as e:  # noqa: BLE001 - demotion is best-effort
                for _, node, handle in pages[handed:]:
                    self._failed(node, handle, e)

    def _hand_off(self, part, blocks) -> None:
        """Give one gathered group (a [G, ...] array of every per-page
        pool) to the stash's thread, first waiting for the oldest hand-offs
        while the staged bytes are over the cap."""
        st = self.counters
        nbytes = sum(handle["nbytes"] for _, _, handle in part)
        while (self._handoffs
               and self.staged_bytes + nbytes > self.staged_cap_bytes):
            t0 = time.perf_counter()
            concurrent.futures.wait([self._handoffs[0][0]])
            st["demote_wait_s"] += time.perf_counter() - t0
            self.reap()
        done = self.stash.put([h for _, _, h in part], *blocks)
        self._handoffs.append((done, part, nbytes))
        for row, (_, _, handle) in enumerate(part):
            self._staged[handle["oid"]] = (blocks, row)
        self.staged_bytes += nbytes
        st["demote_bytes"] += nbytes
        st["demote_inflight_max_bytes"] = max(
            st["demote_inflight_max_bytes"], self.staged_bytes)

    def reap(self) -> None:
        """Let go of the staged copy of every hand-off the stash's thread
        has finished, oldest first, and report to the page manager each
        page that an exception over there kept out of the stash."""
        while self._handoffs and self._handoffs[0][0].done():
            done, part, nbytes = self._handoffs.popleft()
            try:
                errors = done.result()
            except Exception as e:  # noqa: BLE001 - the transfer itself
                errors = [e] * len(part)
            for (_, node, handle), error in zip(part, errors):
                del self._staged[handle["oid"]]
                if error is not None:
                    self._failed(node, handle, error)
            self.staged_bytes -= nbytes

    def restore_page(self, handle: Dict[str, Any], pid: int) -> bool:
        """radix restore_cb: fetch the demoted page's blocks, one of every
        per-page pool (bit-exact — the stash round-trips raw bytes, and a
        page still on its way there is read from its staged copy, waiting
        for the transfer if it must) and STAGE it; `flush_restores` lands
        the staged pages by groups right after the allocation. A per-page
        eager .at[].set would rewrite the whole pool buffer per page, making
        restore cost rival the prefill it avoids."""
        with phase(self._phases, "restore"):
            staged = self._staged.get(handle["oid"])
            if staged is not None:
                groups, row = staged
                blocks = tuple(np.asarray(g)[row] for g in groups)
                self.counters["restored_in_flight"] += 1
            else:
                blocks = self.stash.get(handle)
            self._pending_restores.append((pid, blocks))
        return True

    def flush_restores(self, cache):
        """Land all staged restores in `cache`, a group of DEMOTE_GROUP
        pages a call of the one donated scatter program (a short group is
        padded with zeros for the placeholder page 0), and return the
        result. Must run before prefill reads the pool (called from the
        allocate path); the page manager already counts these pages as
        cached."""
        if not self._pending_restores:
            return cache
        with phase(self._phases, "restore"):
            staged, self._pending_restores = self._pending_restores, []
            for i in range(0, len(staged), DEMOTE_GROUP):
                part = staged[i:i + DEMOTE_GROUP]
                pad = DEMOTE_GROUP - len(part)
                idx = np.zeros((DEMOTE_GROUP,), np.int32)
                idx[:len(part)] = [pid for pid, _ in part]
                cache = self._scatter(cache, idx, tuple(
                    np.stack([blocks[j] for _, blocks in part]
                             + [np.zeros_like(part[0][1][j])] * pad)
                    for j in range(len(self._layout))))
        return cache

    def drop_page(self, handle: Dict[str, Any]) -> None:
        self.stash.drop(handle)

    def close(self) -> None:
        """Wait for what the stash's thread was handed, let go of it, and
        close the stash: no segment and no spill file is left. Closing
        twice is fine."""
        concurrent.futures.wait([h[0] for h in self._handoffs])
        self.reap()
        self.stash.close()


class KVDataServer:
    """Serves sealed KV segments over the ObjectDataServer wire protocol
    (``RTPU1 <token>`` auth, then ranged ``GET <oid> <offset> <length>``)
    so ``node_agent.parallel_fetch`` multi-stream pulls work against a
    serve replica that has no controller object-table entry. Requests
    name the segment's WIRE id; the server translates to the storage
    segment before reading."""

    _DATA_CHUNK = 1 << 20

    def __init__(self, writer: ShipWriter):
        self._writer = writer
        self.addr = ""
        self.serve_bytes = 0
        self._server = None

    async def start(self, host: Optional[str] = None) -> str:
        host = host or os.environ.get("RAY_TPU_KV_HOST", "127.0.0.1")
        self._server = await asyncio.start_server(self._on_client, host, 0)
        port = self._server.sockets[0].getsockname()[1]
        adv = _socket.gethostname() if host not in (
            "127.0.0.1", "localhost", "::1") else "127.0.0.1"
        self.addr = f"{adv}:{port}"
        return self.addr

    def close(self) -> None:
        if self._server is not None:
            self._server.close()
            self._server = None

    def _resolve(self, oid: str) -> Optional[str]:
        if oid.endswith("w"):
            storage = oid[:-1] + "s"
            if self._writer.size_of(storage) is not None:
                return storage
        return None

    async def _on_client(self, reader, writer):
        import hmac

        from ray_tpu._private.cluster import cluster_token
        try:
            hello = await asyncio.wait_for(reader.readline(), timeout=10)
            expect = f"RTPU1 {cluster_token()}\n".encode()
            if not hmac.compare_digest(hello, expect):
                writer.close()
                return
            while True:
                line = await reader.readline()
                if not line:
                    break
                parts = line.decode("ascii", "replace").split()
                if parts[:1] != ["GET"] or len(parts) != 4:
                    break
                await self._serve_range(writer, parts[1], int(parts[2]),
                                        int(parts[3]))
        except (OSError, asyncio.TimeoutError, UnicodeDecodeError, ValueError):
            pass
        finally:
            try:
                writer.close()
            except OSError:
                pass

    async def _serve_range(self, writer, oid: str, offset: int, length: int):
        storage = self._resolve(oid)
        size = self._writer.size_of(storage) if storage else None
        if (size is None or offset < 0 or length <= 0
                or offset + length > size):
            writer.write(b"MISS\n")
            await writer.drain()
            return
        try:
            blob = self._writer.store.read_range(storage, offset, length)
        except Exception:  # noqa: BLE001 - segment vanished under us
            writer.write(b"MISS\n")
            await writer.drain()
            return
        writer.write(f"OK {len(blob)}\n".encode("ascii"))
        for i in range(0, len(blob), self._DATA_CHUNK):
            writer.write(blob[i:i + self._DATA_CHUNK])
            await writer.drain()  # backpressure per chunk
        self.serve_bytes += len(blob)


# Mappings whose close() hit a live export — the CPU jax client releases
# an aliased upload buffer asynchronously, so the detach can trail the
# install by a few events. Holding the handle here (instead of dropping it)
# keeps SharedMemory.__del__ from raising at GC; each later close attempt
# retries the pool.
_pending_close: List[Any] = []


def _drain_pending_close() -> None:
    still = []
    for shm in _pending_close:
        try:
            shm.close()
        except BufferError:
            still.append(shm)
    _pending_close[:] = still


def _final_drain() -> None:
    import gc
    gc.collect()  # collect dead device buffers so their exports release
    _drain_pending_close()


import atexit  # noqa: E402  (registration belongs right next to the pool)

atexit.register(_final_drain)


class AttachedSegment:
    """One pulled segment exposed as zero-copy arrays, `blocks`: one of
    every per-page pool, in the pool's layout with the segment's pages
    along its page axis ([L,Kh,n,ps,D] k and v for the dense layout).

    Close ONLY after the install consumed the arrays; a pulled local
    copy (delete=True) is unlinked on close, a direct attach to the
    writer's segment is merely detached (the writer owns deletion)."""

    def __init__(self, blocks, shm=None,
                 store: Optional[StoreClient] = None,
                 oid: Optional[str] = None, delete: bool = False):
        self.blocks = tuple(blocks)
        self._shm = shm
        self._store = store
        self._oid = oid
        self._delete = delete

    def close(self) -> None:
        self.blocks = ()
        if self._delete and self._store is not None and self._oid:
            # unlink the name now — the open mapping stays valid (POSIX),
            # and the reclaim must not depend on the detach below landing
            self._store.delete_segment(self._oid)
            self._delete = False
        if self._shm is not None:
            shm, self._shm = self._shm, None
            try:
                shm.close()
            except BufferError:
                _pending_close.append(shm)
        _drain_pending_close()


def _carve(buf, seg: Dict[str, Any], layout) -> Tuple[np.ndarray, ...]:
    """Split one segment's bytes into its blocks, one a pool of `layout`
    (the header's `ops.paged_attention.page_layout`), each with the
    segment's n pages at the entry's `axis`."""
    n = seg["n_pages"]
    out, at = _read_blocks(buf, [
        (pool["shape"][:pool["axis"]] + [n] + pool["shape"][pool["axis"]:],
         _np_dtype(pool["dtype"])) for pool in layout])
    if at != seg["nbytes"]:
        raise ValueError(f"segment {seg.get('oid')} holds {seg['nbytes']} "
                         f"bytes, its layout reads {at}")
    return out


class ShipReader:
    """Decode-side segment puller. One per decode replica; owns a pershm
    StoreClient that parallel_fetch lands remote segments into."""

    def __init__(self):
        self.store = StoreClient(backend="pershm")

    async def fetch(self, seg: Dict[str, Any], layout,
                    data_addr: Optional[str] = None,
                    rpc_fetch=None) -> AttachedSegment:
        """Materialize one segment (`layout`: the header's `page_layout`):
        shm attach → parallel_fetch → RPC."""
        att = self._attach(seg["oid"], seg, layout, delete=False)
        if att is not None:
            _counter("kv_ship_attach_hits",
                     "KV segments attached zero-copy same-host").inc()
            return att
        if data_addr:
            from ray_tpu._private.node_agent import parallel_fetch
            got = await parallel_fetch([data_addr], seg["wire"],
                                       seg["nbytes"], 0, (), self.store)
            if got is not None:
                att = self._attach(seg["wire"], seg, layout, delete=True)
                if att is not None:
                    _counter("kv_ship_stream_pulls",
                             "KV segments pulled via parallel_fetch").inc()
                    return att
        if rpc_fetch is not None:
            blob = await rpc_fetch(seg["oid"])
            _counter("kv_ship_rpc_pulls",
                     "KV segments fetched via the RPC fallback").inc()
            return AttachedSegment(_carve(blob, seg, layout))
        raise RuntimeError(
            f"kv segment {seg['oid']} unreachable: no shm attach, no data "
            "server, no RPC fetch")

    def _attach(self, oid: str, seg, layout,
                delete: bool) -> Optional[AttachedSegment]:
        from multiprocessing import shared_memory
        try:
            shm = shared_memory.SharedMemory(name=seg_name(oid))
        except FileNotFoundError:
            return None
        if shm.buf.nbytes < seg["nbytes"]:
            shm.close()
            return None
        return AttachedSegment(_carve(shm.buf, seg, layout), shm=shm,
                               store=self.store, oid=oid, delete=delete)
