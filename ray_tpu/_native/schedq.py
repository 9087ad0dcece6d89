"""ctypes binding for the C++ scheduler ready-queue (src/sched_queue.cpp).

`ReadyQueue` is the controller-facing API: tasks are pushed with a
scheduling signature (pool, resource demand), `next_dispatchable()` returns
the earliest task whose demand fits its pool (optionally masked by
signature), and claims/releases keep the C++ pool mirror in sync with the
controller's dict accounting. `PyReadyQueue` is the semantically identical
pure-Python fallback used when the toolchain is unavailable (and as the
oracle in the equivalence tests).

Build: on-demand g++, cached next to the source keyed by mtime — same
recipe as the shm store binding (_native/store.py).
"""

import ctypes
import os
import threading
from typing import Dict, List, Optional, Tuple

from . import build

_lock = threading.Lock()
_lib = None
_build_error: Optional[str] = None


def _compile() -> str:
    return build("sched_queue")


def _load():
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        try:
            lib = ctypes.CDLL(_compile())
        except Exception as e:  # noqa: BLE001 - fall back to Python queue
            _build_error = str(e)
            return None
        lib.sq_create.restype = ctypes.c_void_p
        lib.sq_destroy.argtypes = [ctypes.c_void_p]
        lib.sq_set_pool.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                    ctypes.POINTER(ctypes.c_int32),
                                    ctypes.POINTER(ctypes.c_double),
                                    ctypes.c_int32]
        lib.sq_remove_pool.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.sq_adjust.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                  ctypes.c_int32, ctypes.c_double]
        lib.sq_register_sig.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                        ctypes.POINTER(ctypes.c_int32),
                                        ctypes.POINTER(ctypes.c_double),
                                        ctypes.c_int32]
        lib.sq_register_sig.restype = ctypes.c_int32
        lib.sq_retire_sig.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.sq_push.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32]
        lib.sq_remove.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.sq_pending.argtypes = [ctypes.c_void_p]
        lib.sq_pending.restype = ctypes.c_int64
        lib.sq_pending_sig.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.sq_pending_sig.restype = ctypes.c_int64
        lib.sq_next.argtypes = [ctypes.c_void_p,
                                ctypes.POINTER(ctypes.c_uint8),
                                ctypes.c_int32,
                                ctypes.POINTER(ctypes.c_int32)]
        lib.sq_next.restype = ctypes.c_int64
        lib.sq_schedule.argtypes = [ctypes.c_void_p,
                                    ctypes.POINTER(ctypes.c_uint8),
                                    ctypes.POINTER(ctypes.c_int32),
                                    ctypes.c_int32,
                                    ctypes.POINTER(ctypes.c_int32),
                                    ctypes.c_int32,
                                    ctypes.POINTER(ctypes.c_int64),
                                    ctypes.POINTER(ctypes.c_int32),
                                    ctypes.c_int32,
                                    ctypes.POINTER(ctypes.c_int64)]
        lib.sq_schedule.restype = ctypes.c_int64
        lib.sq_pop_task.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.sq_pool_avail.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                      ctypes.c_int32]
        lib.sq_pool_avail.restype = ctypes.c_double
        _lib = lib
        return _lib


def _vecs(demand: Dict[int, float]):
    n = len(demand)
    rids = (ctypes.c_int32 * n)(*demand.keys())
    amts = (ctypes.c_double * n)(*demand.values())
    return rids, amts, n


class ReadyQueue:
    """C++-backed signature-bucketed ready queue."""

    def __init__(self):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native sched_queue unavailable: {_build_error}")
        self._lib = lib
        self._h = lib.sq_create()
        self._interned: Dict[str, int] = {}

    def close(self):
        if self._h is not None:
            self._lib.sq_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 - interpreter teardown
            pass

    # -- resource-name interning (C side works on int32 ids) ----------------
    def rid(self, name: str) -> int:
        if name not in self._interned:
            self._interned[name] = len(self._interned)
        return self._interned[name]

    def _demand_ids(self, need: Dict[str, float]) -> Dict[int, float]:
        return {self.rid(k): float(v) for k, v in need.items()}

    # -- pools --------------------------------------------------------------
    def set_pool(self, pool_id: int, avail: Dict[str, float]):
        rids, amts, n = _vecs(self._demand_ids(avail))
        self._lib.sq_set_pool(self._h, pool_id, rids, amts, n)

    def remove_pool(self, pool_id: int):
        self._lib.sq_remove_pool(self._h, pool_id)

    def adjust(self, pool_id: int, need: Dict[str, float], sign: float):
        for rid, amt in self._demand_ids(need).items():
            self._lib.sq_adjust(self._h, pool_id, rid, sign * amt)

    def pool_avail(self, pool_id: int, resource: str) -> float:
        return self._lib.sq_pool_avail(self._h, pool_id, self.rid(resource))

    # -- signatures / tasks -------------------------------------------------
    def register_sig(self, pool_id: int, need: Dict[str, float]) -> int:
        rids, amts, n = _vecs(self._demand_ids(need))
        return self._lib.sq_register_sig(self._h, pool_id, rids, amts, n)

    def retire_sig(self, sig_id: int):
        self._lib.sq_retire_sig(self._h, sig_id)

    def push(self, task_seq: int, sig_id: int):
        self._lib.sq_push(self._h, task_seq, sig_id)

    def remove(self, task_seq: int):
        self._lib.sq_remove(self._h, task_seq)

    def pending(self) -> int:
        return self._lib.sq_pending(self._h)

    def pending_sig(self, sig_id: int) -> int:
        return self._lib.sq_pending_sig(self._h, sig_id)

    def next_dispatchable(self, sig_mask: Optional[List[bool]] = None
                          ) -> Tuple[int, int]:
        """(task_seq, sig_id) of the earliest fitting task, or (-1, -1)."""
        out_sig = ctypes.c_int32(-1)
        if sig_mask is None:
            seq = self._lib.sq_next(self._h, None, 0, ctypes.byref(out_sig))
        else:
            mask = (ctypes.c_uint8 * len(sig_mask))(*[1 if m else 0
                                                      for m in sig_mask])
            seq = self._lib.sq_next(self._h, mask, len(sig_mask),
                                    ctypes.byref(out_sig))
        return seq, out_sig.value

    def pop_task(self, task_seq: int):
        self._lib.sq_pop_task(self._h, task_seq)

    def schedule_batch(self, sig_modes: List[int], sig_buckets: List[int],
                       bucket_idle: List[int], max_out: int = 1024
                       ) -> Tuple[List[Tuple[int, int]], int, int]:
        """Batched scheduling pass under a single GIL release.

        sig_modes[i]: 0 skip, 1 plain (needs idle worker in its bucket),
        2 python-handled barrier (actor creation). sig_buckets[i] indexes
        bucket_idle (idle-worker count per (tpu, env) class; -1 for mode 2).
        Pops + claims every decision natively. Returns
        (decisions [(seq, sig), ...], barrier_sig, barrier_seq) where
        barrier_sig == -1 means the pass ran to exhaustion.
        """
        n = len(sig_modes)
        modes = (ctypes.c_uint8 * n)(*sig_modes)
        buckets = (ctypes.c_int32 * n)(*sig_buckets)
        nb = len(bucket_idle)
        idle = (ctypes.c_int32 * max(nb, 1))(*bucket_idle)
        out_seqs = (ctypes.c_int64 * max_out)()
        out_sigs = (ctypes.c_int32 * max_out)()
        barrier = (ctypes.c_int64 * 2)(-1, -1)
        cnt = self._lib.sq_schedule(self._h, modes, buckets, n, idle, nb,
                                    out_seqs, out_sigs, max_out, barrier)
        decisions = [(out_seqs[i], out_sigs[i]) for i in range(cnt)]
        return decisions, int(barrier[0]), int(barrier[1])


class PyReadyQueue:
    """Pure-Python mirror of ReadyQueue (fallback + test oracle)."""

    _EPS = 1e-9

    def __init__(self):
        self._pools: Dict[int, Dict[str, float]] = {}
        self._sigs: List[Tuple[int, Dict[str, float], List[int]]] = []
        self._free_sigs: List[int] = []
        self._live: Dict[int, int] = {}   # sig -> live count
        self._alive: Dict[int, int] = {}  # seq -> sig

    def close(self):
        pass

    def rid(self, name: str) -> int:  # parity no-op
        return 0

    def set_pool(self, pool_id, avail):
        self._pools[pool_id] = dict(avail)

    def remove_pool(self, pool_id):
        self._pools.pop(pool_id, None)

    def adjust(self, pool_id, need, sign):
        pool = self._pools.setdefault(pool_id, {})
        for k, v in need.items():
            pool[k] = pool.get(k, 0.0) + sign * float(v)

    def pool_avail(self, pool_id, resource):
        return self._pools.get(pool_id, {}).get(resource, 0.0)

    def register_sig(self, pool_id, need):
        if self._free_sigs:
            sig = self._free_sigs.pop()
            self._sigs[sig] = (pool_id, dict(need), [])
        else:
            self._sigs.append((pool_id, dict(need), []))
            sig = len(self._sigs) - 1
        self._live[sig] = 0
        return sig

    def retire_sig(self, sig_id):
        for seq in self._sigs[sig_id][2]:
            self._alive.pop(seq, None)
        self._sigs[sig_id] = (self._sigs[sig_id][0], {}, [])
        self._live[sig_id] = 0
        self._free_sigs.append(sig_id)

    def push(self, task_seq, sig_id):
        self._sigs[sig_id][2].append(task_seq)
        self._alive[task_seq] = sig_id
        self._live[sig_id] += 1

    def remove(self, task_seq):
        sig = self._alive.pop(task_seq, None)
        if sig is not None:
            self._live[sig] -= 1

    def pending(self):
        return len(self._alive)

    def pending_sig(self, sig_id):
        return self._live.get(sig_id, 0)

    def _fits(self, pool_id, need):
        # absent pool -> never fits (MUST match sq_next's pools.find skip,
        # even for zero-demand signatures)
        pool = self._pools.get(pool_id)
        if pool is None:
            return False
        return all(pool.get(k, 0.0) + self._EPS >= v for k, v in need.items())

    def next_dispatchable(self, sig_mask=None):
        best = (-1, -1)
        for i, (pool_id, need, fifo) in enumerate(self._sigs):
            if sig_mask is not None and i < len(sig_mask) and not sig_mask[i]:
                continue
            while fifo and fifo[0] not in self._alive:
                fifo.pop(0)
            if not fifo:
                continue
            if best[0] != -1 and fifo[0] >= best[0]:
                continue
            if self._fits(pool_id, need):
                best = (fifo[0], i)
        return best

    def pop_task(self, task_seq):
        sig = self._alive.pop(task_seq, None)
        if sig is not None:
            self._live[sig] -= 1
            try:
                self._sigs[sig][2].remove(task_seq)
            except ValueError:
                pass

    def schedule_batch(self, sig_modes, sig_buckets, bucket_idle,
                       max_out=1024):
        # semantically identical to sq_schedule (see ReadyQueue) — the
        # randomized equivalence tests drive both with the same sequences
        idle = list(bucket_idle)
        decisions = []
        while len(decisions) < max_out:
            best_seq, best_sig = -1, -1
            for i, (pool_id, need, fifo) in enumerate(self._sigs):
                if i >= len(sig_modes):
                    break
                mode = sig_modes[i]
                if not mode:
                    continue
                while fifo and fifo[0] not in self._alive:
                    fifo.pop(0)
                if not fifo:
                    continue
                if best_seq != -1 and fifo[0] >= best_seq:
                    continue
                if mode == 1:
                    b = sig_buckets[i]
                    if b < 0 or b >= len(idle) or idle[b] <= 0:
                        continue
                if not self._fits(pool_id, need):
                    continue
                best_seq, best_sig = fifo[0], i
            if best_seq == -1:
                return decisions, -1, -1
            if sig_modes[best_sig] == 2:
                return decisions, best_sig, best_seq
            pool_id, need, fifo = self._sigs[best_sig]
            fifo.pop(0)
            self._alive.pop(best_seq, None)
            self._live[best_sig] -= 1
            pool = self._pools.setdefault(pool_id, {})
            for k, v in need.items():
                pool[k] = pool.get(k, 0.0) - float(v)
            idle[sig_buckets[best_sig]] -= 1
            decisions.append((best_seq, best_sig))
        return decisions, -1, -1


def make_ready_queue():
    """ReadyQueue if the native build works, else PyReadyQueue."""
    if os.environ.get("RAY_TPU_NO_NATIVE_SCHEDQ"):
        return PyReadyQueue()
    try:
        return ReadyQueue()
    except RuntimeError:
        return PyReadyQueue()
