"""ctypes binding for the C++ slab store (src/shm_store.cpp).

Build: on-demand `g++ -O2 -shared -fPIC`, cached next to the source keyed by
mtime. The arena is one POSIX shm segment; `SlabStore.view(offset, size)`
returns a zero-copy memoryview into it.
"""

import ctypes
import os
import threading
from typing import Optional

from . import build

_lock = threading.Lock()
_lib = None
_build_error: Optional[str] = None


def _compile() -> str:
    return build("shm_store", "-lpthread", "-lrt")


def _load():
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        try:
            so = _compile()
            lib = ctypes.CDLL(so)
        except Exception as e:  # noqa: BLE001 - toolchain missing → fallback
            _build_error = str(e)
            return None
        lib.rt_store_open.restype = ctypes.c_void_p
        lib.rt_store_open.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                      ctypes.c_int]
        lib.rt_store_close.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.rt_store_alloc.restype = ctypes.c_int64
        lib.rt_store_alloc.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                       ctypes.c_uint64]
        lib.rt_store_lookup.restype = ctypes.c_int64
        lib.rt_store_lookup.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                        ctypes.POINTER(ctypes.c_uint64)]
        lib.rt_store_free.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.rt_store_lookup_pin.restype = ctypes.c_int64
        lib.rt_store_lookup_pin.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                            ctypes.POINTER(ctypes.c_uint64)]
        lib.rt_store_unpin.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.rt_store_release_pins.restype = ctypes.c_int
        lib.rt_store_release_pins.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.rt_store_used.restype = ctypes.c_uint64
        lib.rt_store_used.argtypes = [ctypes.c_void_p]
        lib.rt_store_num_objects.restype = ctypes.c_uint64
        lib.rt_store_num_objects.argtypes = [ctypes.c_void_p]
        lib.rt_store_capacity.restype = ctypes.c_uint64
        lib.rt_store_capacity.argtypes = [ctypes.c_void_p]
        lib.rt_store_base.restype = ctypes.c_void_p
        lib.rt_store_base.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


class SlabStore:
    """One process's view of a shared arena."""

    def __init__(self, name: str, capacity: int = 0, create: bool = False):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native store unavailable: {_build_error}")
        self._lib = lib
        self.name = name
        self._h = lib.rt_store_open(name.encode(), capacity, 1 if create else 0)
        if not self._h:
            raise OSError(f"could not open shm arena {name!r}")
        self._base = lib.rt_store_base(self._h)

    # -- allocation ----------------------------------------------------------
    def alloc(self, key: str, size: int) -> int:
        off = self._lib.rt_store_alloc(self._h, key.encode(), size)
        if off < 0:
            raise MemoryError(
                f"arena full allocating {size} bytes for {key} "
                f"(used {self.used()}/{self.capacity()})")
        return off

    def lookup(self, key: str):
        size = ctypes.c_uint64()
        off = self._lib.rt_store_lookup(self._h, key.encode(),
                                        ctypes.byref(size))
        if off < 0:
            return None
        return off, size.value

    def free(self, key: str) -> bool:
        return self._lib.rt_store_free(self._h, key.encode()) == 0

    def lookup_pin(self, key: str):
        """Atomically look up AND pin: the block's memory stays valid (even
        across free) until the matching `unpin(offset)`."""
        size = ctypes.c_uint64()
        off = self._lib.rt_store_lookup_pin(self._h, key.encode(),
                                            ctypes.byref(size))
        if off < 0:
            return None
        return off, size.value

    def unpin(self, offset: int) -> None:
        if self._h:
            self._lib.rt_store_unpin(self._h, offset)

    def release_pins(self, pid: int) -> int:
        """Drop every pin held by `pid` (plasma disconnect-cleanup parity);
        returns how many were released."""
        if self._h:
            return self._lib.rt_store_release_pins(self._h, pid)
        return 0

    # -- zero-copy access ----------------------------------------------------
    def view(self, offset: int, size: int) -> memoryview:
        buf = (ctypes.c_ubyte * size).from_address(self._base + offset)
        return memoryview(buf).cast("B")

    def write(self, offset: int, data) -> None:
        mv = self.view(offset, len(data) if hasattr(data, "__len__")
                       else data.nbytes)
        mv[:] = data

    # -- stats ---------------------------------------------------------------
    def used(self) -> int:
        return self._lib.rt_store_used(self._h)

    def num_objects(self) -> int:
        return self._lib.rt_store_num_objects(self._h)

    def capacity(self) -> int:
        return self._lib.rt_store_capacity(self._h)

    def close(self, unlink: bool = False):
        if self._h:
            self._lib.rt_store_close(self._h, 1 if unlink else 0)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 - interpreter teardown
            pass
