"""Serialization with zero-copy out-of-band buffers.

Reference: python/ray/_private/serialization.py — Ray serializes with
cloudpickle protocol 5 and ships large buffers (numpy arrays, arrow blocks)
out-of-band into plasma so `get()` can map them zero-copy.

We do the same: `dumps_oob` returns (pickle_bytes, [raw buffers]); callers lay
the buffers into shared memory and `loads_oob` reconstructs with memoryviews
into that shm — numpy arrays then alias the segment with no copy.

Host values cross process boundaries: a `jax.Array` is written as the numpy
array it holds. jax's own pickle support re-uploads with `device_put` inside
`loads`, which makes whoever unpickles — typically the driver, reading a
`report()` or a task result — initialize a backend; on libtpu that is an
attempt to open the chip the producing worker still owns. The consumer gets
numpy and uploads when (and if) it computes.
"""

import io
import pickle
import struct
import sys
import threading

import cloudpickle

# Buffers below this size get folded in-band: the bookkeeping costs more than
# the copy.
_OOB_MIN_BYTES = 4096

# Nested-ObjectRef collection (ref: Ray's "contained object IDs",
# src/ray/core_worker/reference_count.h AddNestedObjectIds). While a
# serialization is active, ObjectRef.__reduce__ records its id here; the
# caller pins those ids on behalf of the containing object/task so GC of the
# sender's ref can't evict an object still reachable through serialized bytes.
_collector = threading.local()


def note_contained_ref(object_id: str) -> None:
    ids_ = getattr(_collector, "ids", None)
    if ids_ is not None:
        ids_.append(object_id)


class _CollectRefs:
    def __enter__(self):
        self._prev = getattr(_collector, "ids", None)
        _collector.ids = []
        return _collector.ids

    def __exit__(self, *a):
        _collector.ids = self._prev


class _HostValuePickler(cloudpickle.Pickler):
    """cloudpickle, except that device arrays go out as host arrays."""

    def reducer_override(self, obj):
        jax = sys.modules.get("jax")  # no jax in this process → no jax.Array
        if (jax is not None and isinstance(obj, jax.Array)
                and not jax.dtypes.issubdtype(obj.dtype, jax.dtypes.prng_key)):
            import numpy as np
            return np.asarray(obj).__reduce_ex__(pickle.HIGHEST_PROTOCOL)
        return super().reducer_override(obj)


def _dumps(obj, buffer_callback=None) -> bytes:
    with io.BytesIO() as f:
        _HostValuePickler(f, protocol=5,
                          buffer_callback=buffer_callback).dump(obj)
        return f.getvalue()


# Exact-type scalars take the plain-pickle fast path below: no cloudpickle
# machinery, no buffer callback, no ref collection. Protocol-5 pickling of
# these types never emits out-of-band buffers and the values cannot contain
# ObjectRefs, so the result is byte-for-byte what the slow path would build.
_SCALAR_TYPES = (int, float, bool, str, bytes, type(None))


_SCALAR_HDR = struct.Struct("<II")


def pack_scalar(obj) -> bytes:
    """pack_parts(dumps_oob(scalar)) fused into one concatenation: the packed
    form of a buffer-free value is u32 meta_len | u32 npickle | pickle, so
    for exact-type scalars (the dominant task-arg shape) both headers can be
    emitted in a single struct call with no intermediate bytearray. Callers
    on the submit hot path (RemoteFunction.remote's fast arg loop) use this;
    byte-for-byte identical to the generic path."""
    payload = pickle.dumps(obj, 5)
    n = len(payload)
    return _SCALAR_HDR.pack(n + 4, n) + payload


def dumps_oob(obj):
    """Serialize to (meta_bytes, list_of_buffers, contained_ref_ids).

    meta_bytes layout: u32 npickle | pickle | (u64 size)*nbuf — self-framing so
    a single contiguous shm write round-trips.
    """
    if type(obj) in _SCALAR_TYPES:
        payload = pickle.dumps(obj, protocol=5)
        return struct.pack("<I", len(payload)) + payload, [], []
    buffers = []

    def callback(buf):
        raw = buf.raw()
        if raw.nbytes < _OOB_MIN_BYTES:
            return True  # keep small buffers in-band
        buffers.append(raw)
        return False

    with _CollectRefs() as contained:
        payload = _dumps(obj, buffer_callback=callback)
    header = struct.pack("<I", len(payload)) + payload
    for b in buffers:
        header += struct.pack("<Q", b.nbytes)
    return header, buffers, list(contained)


def pack_with_refs(obj):
    """Serialize to one contiguous bytes blob + the nested ObjectRef ids found
    during serialization. There is deliberately no ref-blind `pack()`:
    dropping the contained list reopens the sender-GC eviction race."""
    meta, buffers, contained = dumps_oob(obj)
    return pack_parts(meta, buffers), contained


def dumps_with_refs(obj):
    """cloudpickle.dumps + contained ObjectRef ids (for function/class blobs
    that may capture refs in closures or globals)."""
    with _CollectRefs() as contained:
        blob = _dumps(obj)
    return blob, list(contained)


def pack_parts(meta: bytes, buffers) -> bytearray:
    # Sized once and written in place: BytesIO + getvalue() grew the internal
    # buffer and then copied the whole blob a second time.
    out = bytearray(4 + len(meta) + sum(b.nbytes for b in buffers))
    struct.pack_into("<I", out, 0, len(meta))
    pos = 4
    out[pos : pos + len(meta)] = meta
    pos += len(meta)
    for b in buffers:
        out[pos : pos + b.nbytes] = b
        pos += b.nbytes
    return out


def unpack(data) -> object:
    """Inverse of pack; accepts bytes or memoryview (zero-copy for the latter)."""
    mv = memoryview(data)
    (meta_len,) = struct.unpack_from("<I", mv, 0)
    meta = mv[4 : 4 + meta_len]
    return loads_oob(meta, mv[4 + meta_len :])


def loads_oob(meta, tail) -> object:
    """Reconstruct from self-framing meta + a memoryview holding the buffers.

    `tail` must start at the first out-of-band buffer. Buffers are passed to
    pickle as sub-memoryviews — no copies.
    """
    mv = memoryview(meta)
    (npickle,) = struct.unpack_from("<I", mv, 0)
    payload = mv[4 : 4 + npickle]
    sizes = []
    off = 4 + npickle
    while off < mv.nbytes:
        (sz,) = struct.unpack_from("<Q", mv, off)
        sizes.append(sz)
        off += 8
    bufs = []
    t = memoryview(tail)
    pos = 0
    for sz in sizes:
        # read-only: consumers alias shared memory (ref: plasma objects are
        # immutable once sealed)
        bufs.append(pickle.PickleBuffer(t[pos : pos + sz].toreadonly()))
        pos += sz
    return pickle.loads(payload, buffers=bufs)


def total_size(meta: bytes, buffers) -> int:
    return len(meta) + sum(b.nbytes for b in buffers)
