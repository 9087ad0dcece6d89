"""ctypes binding for the id-sharded object/actor directory
(src/obj_directory.cpp).

`ObjectDirectory` holds the counter state of the control plane — refcount,
pin count, size, location, holder set — keyed by id-hash shard with a lock
per shard, so heartbeat holds-object updates, prefetch location lookups and
decref storms stop serializing on one GIL-bound dict. The controller's
ObjectMeta delegates its counter fields here (task_spec.py); the rich Python
state (inline bytes, errors, asyncio events) stays on the meta.

`apply_deltas` consumes a packed incref/decref run — the same byte layout
the frame codec ships as a "refdeltas" batch entry — in one GIL-releasing
call and reports which ids were newly released / became evictable.

`PyObjectDirectory` is the semantically identical pure-Python fallback used
when the toolchain is unavailable (and as the oracle in the equivalence
tests, tests/test_objdir.py). Build: on-demand g++ cached next to the
source keyed by mtime — same recipe as the sched-queue binding.
"""

import ctypes
import os
import struct
import threading
from typing import Dict, List, Optional, Tuple

from . import build

_lock = threading.Lock()
_lib = None        # PyDLL handle: scalar ops, GIL held
_bulk_lib = None   # CDLL handle: bulk ops, GIL released
_build_error: Optional[str] = None

NUM_SHARDS = int(os.environ.get("RAY_TPU_OBJDIR_SHARDS", "16"))

_MISSING_I64 = -(1 << 63)
_MISSING_I32 = -(1 << 31)

# location string <-> (code, node) mapping; code 6 round-trips any string
# this module doesn't know about (forward compatibility)
_LOC_CODES = {"pending": 0, "shm": 1, "inline": 2, "spilled": 3, "error": 4}
_LOC_NAMES = {v: k for k, v in _LOC_CODES.items()}

INCREF = 1
DECREF = 2
F_RELEASED = 1   # apply_deltas flag: refcount first crossed to <= 0
F_EVICTABLE = 2  # apply_deltas flag: refcount <= 0 and pinned == 0


def _loc_to_pair(location: str) -> Tuple[int, str]:
    code = _LOC_CODES.get(location)
    if code is not None:
        return code, ""
    if location.startswith("remote:"):
        return 5, location.split(":", 1)[1]
    return 6, location


def _pair_to_loc(code: int, node: str) -> str:
    if code == 5:
        return f"remote:{node}"
    if code == 6:
        return node
    return _LOC_NAMES.get(code, "pending")


def pack_deltas(ops) -> bytes:
    """Pack (op, id) pairs — op INCREF/DECREF — into the shared delta-run
    byte layout: repeat{ u8 op | u16 idlen LE | id utf8 }."""
    parts = []
    for op, oid in ops:
        raw = oid.encode()
        parts.append(struct.pack("<BH", op, len(raw)))
        parts.append(raw)
    return b"".join(parts)


def unpack_delta_result(buf) -> List[Tuple[str, int, int]]:
    """Inverse of apply_deltas' output: [(id, flags, final_refcount), ...] —
    one record per touched id so callers can sync mirror caches in the same
    pass that collects eviction verdicts."""
    out = []
    pos = 0
    mv = memoryview(buf)
    while pos < len(mv):
        flags, rc, n = struct.unpack_from("<BqH", mv, pos)
        pos += 11
        out.append((bytes(mv[pos:pos + n]).decode(), flags, rc))
        pos += n
    return out


def _compile() -> str:
    return build("obj_directory")


def _load():
    global _lib, _bulk_lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        try:
            so = _compile()
            # Two handles over the same .so: PyDLL keeps the GIL for the
            # sub-microsecond scalar ops (a GIL release per tiny call just
            # invites a thread switch on the controller loop's hot path);
            # CDLL releases it for the bulk ops (apply_deltas, snapshot,
            # drop_node) where other threads can do real work meanwhile.
            lib = ctypes.PyDLL(so)
            blib = ctypes.CDLL(so)
        except Exception as e:  # noqa: BLE001 - fall back to Python directory
            _build_error = str(e)
            return None
        c = ctypes
        blib.od_drop_node.argtypes = [c.c_void_p, c.c_char_p]
        blib.od_drop_node.restype = c.c_int64
        blib.od_apply_deltas.argtypes = [c.c_void_p, c.c_char_p, c.c_int64,
                                         c.c_char_p, c.c_int64]
        blib.od_apply_deltas.restype = c.c_int64
        blib.od_snapshot.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
        blib.od_snapshot.restype = c.c_int64
        _bulk_lib = blib
        lib.od_create.restype = c.c_void_p
        lib.od_create.argtypes = [c.c_int32]
        lib.od_destroy.argtypes = [c.c_void_p]
        lib.od_nshards.argtypes = [c.c_void_p]
        lib.od_nshards.restype = c.c_int32
        lib.od_register.argtypes = [c.c_void_p, c.c_char_p, c.c_int64,
                                    c.c_int32, c.c_int64, c.c_int32,
                                    c.c_char_p]
        for name in ("od_erase", "od_contains"):
            fn = getattr(lib, name)
            fn.argtypes = [c.c_void_p, c.c_char_p]
            fn.restype = c.c_int32
        lib.od_count.argtypes = [c.c_void_p]
        lib.od_count.restype = c.c_int64
        lib.od_shard_count.argtypes = [c.c_void_p, c.c_int32]
        lib.od_shard_count.restype = c.c_int64
        lib.od_total_bytes.argtypes = [c.c_void_p]
        lib.od_total_bytes.restype = c.c_int64
        lib.od_get_refcount.argtypes = [c.c_void_p, c.c_char_p]
        lib.od_get_refcount.restype = c.c_int64
        lib.od_set_refcount.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
        lib.od_add_refcount.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
        lib.od_add_refcount.restype = c.c_int64
        lib.od_get_pinned.argtypes = [c.c_void_p, c.c_char_p]
        lib.od_get_pinned.restype = c.c_int32
        lib.od_set_pinned.argtypes = [c.c_void_p, c.c_char_p, c.c_int32]
        lib.od_get_size.argtypes = [c.c_void_p, c.c_char_p]
        lib.od_get_size.restype = c.c_int64
        lib.od_set_size.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
        lib.od_set_location.argtypes = [c.c_void_p, c.c_char_p, c.c_int32,
                                        c.c_char_p]
        lib.od_get_location.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p,
                                        c.c_int32]
        lib.od_get_location.restype = c.c_int32
        lib.od_add_holder.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p]
        lib.od_add_holder.restype = c.c_int32
        lib.od_remove_holder.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p]
        lib.od_remove_holder.restype = c.c_int32
        lib.od_clear_holders.argtypes = [c.c_void_p, c.c_char_p]
        lib.od_get_holders.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p,
                                       c.c_int64]
        lib.od_get_holders.restype = c.c_int64
        _lib = lib
        return _lib


class ObjectDirectory:
    """C++-backed id-sharded directory."""

    def __init__(self, nshards: int = NUM_SHARDS):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native obj_directory unavailable: {_build_error}")
        self._lib = lib
        self._blib = _bulk_lib
        self._h = lib.od_create(nshards)
        self.nshards = lib.od_nshards(self._h)

    def close(self):
        if self._h is not None:
            self._lib.od_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 - interpreter teardown
            pass

    def register(self, oid: str, refcount: int = 1, pinned: int = 0,
                 size: int = 0, location: str = "pending"):
        code, node = _loc_to_pair(location)
        self._lib.od_register(self._h, oid.encode(), refcount, pinned, size,
                              code, node.encode())

    def erase(self, oid: str) -> bool:
        return bool(self._lib.od_erase(self._h, oid.encode()))

    def contains(self, oid: str) -> bool:
        return bool(self._lib.od_contains(self._h, oid.encode()))

    def count(self) -> int:
        return self._lib.od_count(self._h)

    def shard_count(self, i: int) -> int:
        return self._lib.od_shard_count(self._h, i)

    def total_bytes(self) -> int:
        return self._lib.od_total_bytes(self._h)

    def refcount(self, oid: str) -> Optional[int]:
        v = self._lib.od_get_refcount(self._h, oid.encode())
        return None if v == _MISSING_I64 else v

    def set_refcount(self, oid: str, v: int):
        self._lib.od_set_refcount(self._h, oid.encode(), v)

    def add_refcount(self, oid: str, delta: int) -> Optional[int]:
        v = self._lib.od_add_refcount(self._h, oid.encode(), delta)
        return None if v == _MISSING_I64 else v

    def pinned(self, oid: str) -> Optional[int]:
        v = self._lib.od_get_pinned(self._h, oid.encode())
        return None if v == _MISSING_I32 else v

    def set_pinned(self, oid: str, v: int):
        self._lib.od_set_pinned(self._h, oid.encode(), v)

    def size(self, oid: str) -> Optional[int]:
        v = self._lib.od_get_size(self._h, oid.encode())
        return None if v == _MISSING_I64 else v

    def set_size(self, oid: str, v: int):
        self._lib.od_set_size(self._h, oid.encode(), v)

    def set_location(self, oid: str, location: str):
        code, node = _loc_to_pair(location)
        self._lib.od_set_location(self._h, oid.encode(), code, node.encode())

    def location(self, oid: str) -> Optional[str]:
        buf = ctypes.create_string_buffer(512)
        r = self._lib.od_get_location(self._h, oid.encode(), buf, 512)
        if r < 0:
            return None
        code, n = r & 0xFF, r >> 8
        return _pair_to_loc(code, buf.raw[:n].decode())

    def add_holder(self, oid: str, node: str) -> bool:
        return bool(self._lib.od_add_holder(self._h, oid.encode(),
                                            node.encode()))

    def remove_holder(self, oid: str, node: str) -> bool:
        return bool(self._lib.od_remove_holder(self._h, oid.encode(),
                                               node.encode()))

    def clear_holders(self, oid: str):
        self._lib.od_clear_holders(self._h, oid.encode())

    def holders(self, oid: str) -> List[str]:
        cap = 1024
        while True:
            buf = ctypes.create_string_buffer(cap)
            r = self._lib.od_get_holders(self._h, oid.encode(), buf, cap)
            if r == -1:
                return []
            if r >= 0:
                if r == 0:
                    return []
                return buf.raw[:r].decode().split("\n")
            cap = -r  # -need - 1 => need + 1 bytes

    def drop_node(self, node: str) -> int:
        return self._blib.od_drop_node(self._h, node.encode())

    def apply_deltas(self, packed) -> List[Tuple[str, int, int]]:
        packed = bytes(packed)
        if not packed:
            return []
        # output records are 8 bytes wider than input records (the i64
        # final refcount rides along); min input record is 3 bytes
        cap = 4 * len(packed) + 16
        out = ctypes.create_string_buffer(cap)
        r = self._blib.od_apply_deltas(self._h, packed, len(packed), out, cap)
        if r == -1:
            raise ValueError("malformed delta run")
        if r == -2:  # can't happen given the cap above, but stay safe
            raise RuntimeError("delta result buffer too small")
        return unpack_delta_result(out.raw[:r])

    def snapshot(self) -> bytes:
        cap = 1 << 16
        while True:
            buf = ctypes.create_string_buffer(cap)
            r = self._blib.od_snapshot(self._h, buf, cap)
            if r >= 0:
                return buf.raw[:r]
            cap = -r  # -need - 1


class _PyEntry:
    __slots__ = ("refcount", "pinned", "size", "loc", "loc_node", "holders",
                 "released")

    def __init__(self, refcount=1, pinned=0, size=0, loc=0, loc_node=""):
        self.refcount = refcount
        self.pinned = pinned
        self.size = size
        self.loc = loc
        self.loc_node = loc_node
        self.holders: List[str] = []
        self.released = 1 if refcount <= 0 else 0


class PyObjectDirectory:
    """Pure-Python mirror of ObjectDirectory (fallback + test oracle):
    same sharding, same per-shard locks, byte-identical snapshot()."""

    def __init__(self, nshards: int = NUM_SHARDS):
        self.nshards = max(nshards, 1)
        self._shards: List[Dict[str, _PyEntry]] = [
            {} for _ in range(self.nshards)]
        self._locks = [threading.Lock() for _ in range(self.nshards)]

    def close(self):
        pass

    @staticmethod
    def _fnv1a(raw: bytes) -> int:
        h = 1469598103934665603
        for b in raw:
            h = ((h ^ b) * 1099511628211) & 0xFFFFFFFFFFFFFFFF
        return h

    def _shard(self, oid: str):
        i = self._fnv1a(oid.encode()) % self.nshards
        return self._shards[i], self._locks[i]

    def register(self, oid, refcount=1, pinned=0, size=0, location="pending"):
        code, node = _loc_to_pair(location)
        m, lk = self._shard(oid)
        with lk:
            m[oid] = _PyEntry(refcount, pinned, size, code, node)

    def erase(self, oid) -> bool:
        m, lk = self._shard(oid)
        with lk:
            return m.pop(oid, None) is not None

    def contains(self, oid) -> bool:
        m, lk = self._shard(oid)
        with lk:
            return oid in m

    def count(self) -> int:
        return sum(len(m) for m in self._shards)

    def shard_count(self, i) -> int:
        if i < 0 or i >= self.nshards:
            return -1
        return len(self._shards[i])

    def total_bytes(self) -> int:
        total = 0
        for m, lk in zip(self._shards, self._locks):
            with lk:
                total += sum(e.size for e in m.values())
        return total

    def refcount(self, oid):
        m, lk = self._shard(oid)
        with lk:
            e = m.get(oid)
            return None if e is None else e.refcount

    def set_refcount(self, oid, v):
        m, lk = self._shard(oid)
        with lk:
            e = m.get(oid)
            if e is None:
                return
            if v <= 0 and e.refcount > 0:
                e.released = 1
            e.refcount = v

    def add_refcount(self, oid, delta):
        m, lk = self._shard(oid)
        with lk:
            e = m.get(oid)
            if e is None:
                return None
            if e.refcount > 0 and e.refcount + delta <= 0:
                e.released = 1
            e.refcount += delta
            return e.refcount

    def pinned(self, oid):
        m, lk = self._shard(oid)
        with lk:
            e = m.get(oid)
            return None if e is None else e.pinned

    def set_pinned(self, oid, v):
        m, lk = self._shard(oid)
        with lk:
            e = m.get(oid)
            if e is not None:
                e.pinned = v

    def size(self, oid):
        m, lk = self._shard(oid)
        with lk:
            e = m.get(oid)
            return None if e is None else e.size

    def set_size(self, oid, v):
        m, lk = self._shard(oid)
        with lk:
            e = m.get(oid)
            if e is not None:
                e.size = v

    def set_location(self, oid, location):
        code, node = _loc_to_pair(location)
        m, lk = self._shard(oid)
        with lk:
            e = m.get(oid)
            if e is not None:
                e.loc, e.loc_node = code, node

    def location(self, oid):
        m, lk = self._shard(oid)
        with lk:
            e = m.get(oid)
            if e is None:
                return None
            return _pair_to_loc(e.loc, e.loc_node)

    def add_holder(self, oid, node) -> bool:
        m, lk = self._shard(oid)
        with lk:
            e = m.get(oid)
            if e is None or node in e.holders:
                return False
            e.holders.append(node)
            return True

    def remove_holder(self, oid, node) -> bool:
        m, lk = self._shard(oid)
        with lk:
            e = m.get(oid)
            if e is None or node not in e.holders:
                return False
            e.holders.remove(node)
            return True

    def clear_holders(self, oid):
        m, lk = self._shard(oid)
        with lk:
            e = m.get(oid)
            if e is not None:
                e.holders = []

    def holders(self, oid):
        m, lk = self._shard(oid)
        with lk:
            e = m.get(oid)
            return [] if e is None else list(e.holders)

    def drop_node(self, node) -> int:
        touched = 0
        for m, lk in zip(self._shards, self._locks):
            with lk:
                for e in m.values():
                    if node in e.holders:
                        e.holders.remove(node)
                        touched += 1
        return touched

    def apply_deltas(self, packed):
        packed = bytes(packed)
        mv = memoryview(packed)
        order: List[str] = []
        touched: List[str] = []
        pos = 0
        while pos < len(mv):
            if pos + 3 > len(mv):
                raise ValueError("malformed delta run")
            op, idlen = struct.unpack_from("<BH", mv, pos)
            pos += 3
            if pos + idlen > len(mv) or op not in (INCREF, DECREF):
                raise ValueError("malformed delta run")
            oid = bytes(mv[pos:pos + idlen]).decode()
            pos += idlen
            m, lk = self._shard(oid)
            with lk:
                e = m.get(oid)
                if e is None:
                    continue
                delta = 1 if op == INCREF else -1
                was = e.released
                if e.refcount > 0 and e.refcount + delta <= 0:
                    e.released = 1
                e.refcount += delta
                if not was and e.released:
                    order.append(oid)
            touched.append(oid)
        newly = set(order)
        out = []
        seen = set()
        for oid in touched:
            if oid in seen:
                continue
            seen.add(oid)
            m, lk = self._shard(oid)
            with lk:
                e = m.get(oid)
                if e is None:
                    continue
                flags = 0
                if oid in newly:
                    flags |= F_RELEASED
                if e.refcount <= 0 and e.pinned == 0:
                    flags |= F_EVICTABLE
                out.append((oid, flags, e.refcount))
        return out

    def snapshot(self) -> bytes:
        all_entries = {}
        for m, lk in zip(self._shards, self._locks):
            with lk:
                all_entries.update(m)
        parts = []
        for oid in sorted(all_entries):
            e = all_entries[oid]
            raw = oid.encode()
            node = e.loc_node.encode()
            parts.append(struct.pack("<H", len(raw)))
            parts.append(raw)
            parts.append(struct.pack("<qiqBH", e.refcount, e.pinned, e.size,
                                     e.loc, len(node)))
            parts.append(node)
            hs = sorted(e.holders)
            parts.append(struct.pack("<BH", e.released, len(hs)))
            for hv in hs:
                hraw = hv.encode()
                parts.append(struct.pack("<H", len(hraw)))
                parts.append(hraw)
        return b"".join(parts)


def native_disabled() -> bool:
    return os.environ.get("RAY_TPU_NATIVE", "").lower() in ("0", "false", "no")


def available() -> bool:
    """True when the native directory builds/loads on this machine."""
    return _load() is not None


def make_object_directory(nshards: int = NUM_SHARDS):
    """ObjectDirectory if the native build works, else PyObjectDirectory.
    `RAY_TPU_NATIVE=0` forces the Python fallback (escape hatch documented
    in README's control-plane section)."""
    if native_disabled():
        return PyObjectDirectory(nshards)
    try:
        return ObjectDirectory(nshards)
    except RuntimeError:
        return PyObjectDirectory(nshards)


# Per-process singleton: ObjectMeta property accessors and the controller's
# bulk delta path must hit the SAME directory instance.
_dir = None
_dir_lock = threading.Lock()


def get_directory():
    global _dir
    if _dir is None:
        with _dir_lock:
            if _dir is None:
                _dir = make_object_directory()
    return _dir


def reset_directory():
    """Drop the process singleton (tests only — a fresh session must not see
    a directory populated by a previous one).

    The old instance must NOT be close()d here: a controller constructed
    earlier in the process keeps its own reference and would be left calling
    into a destroyed native handle. __del__ frees the handle once the last
    reference drops.
    """
    global _dir
    with _dir_lock:
        _dir = None
