"""Length-prefixed framing over unix sockets: pickle + negotiated native codec.

Reference: Ray's control plane is gRPC (src/ray/rpc, src/ray/protobuf). For a
single-host controller a unix socket with length-prefixed framing has lower
latency and zero codegen; the message *vocabulary* mirrors the reference's
core-worker ↔ raylet ↔ GCS RPCs (SubmitTask, PushTask reply,
WaitForObjectEviction, ...).

Frame: u32 little-endian length | payload. Two payload encodings share the
stream, distinguished by the first payload byte:

- pickle of (kind, dict) — starts 0x80 (pickle protocol >= 2). The default,
  and the only encoding for rare kinds (RPCs, replies, heartbeats).
- native codec — starts 0xC3 (_native/codec.py, wire format pinned by
  tests/test_frame_codec.py). Used for high-frequency "batch" frames when
  both ends negotiated codec_ver > 0 in their register handshake.
  RAY_TPU_NATIVE=0 turns this off entirely (all-pickle escape hatch).

Receivers always sniff, so decoding never depends on the negotiation state;
negotiation only governs what a sender may emit.

Pipelined control plane additions:
- the "batch" kind carries a list of coalesced refcount/put/submit/task_done
  entries (see client._DeltaFlusher / controller._apply_batch).
- the "owned" kind is a one-way controller → owner push of result
  descriptors for client-owned small objects (controller._push_owned /
  client._OwnedTable) — the owner's gets then resolve locally with zero
  round trips. "exec" dispatch frames ride the native codec (KIND_EXEC)
  when the worker negotiated codec_ver > 0.
- per-process counters tally frames by kind, blocking round trips, and
  stream reads beside the items they handed over (note_stream_read), read
  through ray_tpu.util.metrics.control_plane_counters(); benchmarks and the
  pipelining tests assert on deltas of these. Counters are kept in
  per-thread tables merged lazily at read time — the old single-lock dict
  serialized every send/recv across threads on the hot path.
"""

import pickle
import struct
import threading
from typing import Dict

from .._native import codec as _codec

_HDR = struct.Struct("<I")

# -- control-plane transport counters (per process) -------------------------
# Plain dicts rather than util.metrics Counters: protocol.py is imported
# while ray_tpu/__init__ is still executing, so it must not pull in
# ray_tpu.util. util/metrics.py re-exposes these lazily.
#
# Sharded per thread: _bump touches only this thread's table (dict ops are
# GIL-atomic, no lock), and readers merge every thread's table under
# _tables_lock. Totals are exact for quiesced threads and at most one frame
# stale for threads mid-send — fine for counters.
_tables_lock = threading.Lock()
_all_tables = []  # [(sent, received, roundtrips, local_gets, streams)] per thread


class _ThreadTables(threading.local):
    def __init__(self):
        self.sent: Dict[str, int] = {}
        self.received: Dict[str, int] = {}
        self.roundtrips: Dict[str, int] = {}
        self.local_gets: Dict[str, int] = {}
        self.streams: Dict[str, int] = {}
        with _tables_lock:
            _all_tables.append((self.sent, self.received, self.roundtrips,
                                self.local_gets, self.streams))


_tls = _ThreadTables()


def _bump_sent(kind: str) -> None:
    t = _tls.sent
    t[kind] = t.get(kind, 0) + 1


def _bump_received(kind: str) -> None:
    t = _tls.received
    t[kind] = t.get(kind, 0) + 1


def note_roundtrip(kind: str) -> None:
    """Record one blocking control round trip (a request that waited for its
    reply — worker `_rpc` or a driver bridge call into the controller loop)."""
    t = _tls.roundtrips
    t[kind] = t.get(kind, 0) + 1


def note_local_get(n: int = 1) -> None:
    """Record owned objects served from the client-LOCAL ownership table —
    gets that touched neither the socket nor the controller loop (the
    ownership model's zero-round-trip path)."""
    t = _tls.local_gets
    t["owned"] = t.get("owned", 0) + n


def note_stream_read(n_items: int) -> None:
    """Record one read of a stream that handed `n_items` items to its reader
    (ObjectRefGenerator). items / reads is 1.0 while readers keep up and
    rises with the backlog a read finds: each read is ONE round trip
    whatever it carries."""
    t = _tls.streams
    t["reads"] = t.get("reads", 0) + 1
    t["items"] = t.get("items", 0) + n_items


def local_gets_total() -> int:
    return sum(_merged(3).values())


def _merged(idx: int) -> Dict[str, int]:
    out: Dict[str, int] = {}
    with _tables_lock:
        tables = [t[idx] for t in _all_tables]
    for table in tables:
        for k, v in list(table.items()):
            out[k] = out.get(k, 0) + v
    return out


def roundtrips_total() -> int:
    return sum(_merged(2).values())


def frames_sent_total() -> int:
    return sum(_merged(0).values())


def counter_snapshot() -> Dict[str, Dict[str, int]]:
    return {"frames_sent": _merged(0),
            "frames_received": _merged(1),
            "roundtrips": _merged(2),
            "local_gets": _merged(3),
            "streams": _merged(4)}


def _encode(kind: str, payload: dict, codec_on: bool) -> bytes:
    if codec_on:
        data = _codec.encode(kind, payload)
        if data is not None:
            return data
    return pickle.dumps((kind, payload), protocol=5)


def _decode(data):
    if data and data[0] == _codec.MAGIC:
        return _codec.decode(data)
    return pickle.loads(data)


def send_msg(sock, kind: str, **payload):
    send_payload(sock, kind, payload)


def send_payload(sock, kind: str, payload: dict, codec_on: bool = False):
    """send_msg with an explicit payload dict + optional codec: high-rate
    senders (the worker client's batch sink) pass codec_on=True once the
    register handshake negotiated codec_ver > 0."""
    data = _encode(kind, payload, codec_on)
    _bump_sent(kind)
    sock.sendall(_HDR.pack(len(data)) + data)


def recv_msg(sock):
    hdr = _recv_exact(sock, 4)
    if hdr is None:
        return None
    (n,) = _HDR.unpack(hdr)
    data = _recv_exact(sock, n)
    if data is None:
        return None
    msg = _decode(data)
    _bump_received(msg[0])
    return msg


def _recv_exact(sock, n):
    # recv_into a preallocated buffer: the old recv()+join built every chunk
    # as a fresh bytes object (two passes over large frames and O(chunks)
    # allocations); this is one allocation and one copy total.
    buf = bytearray(n)
    view = memoryview(buf)
    pos = 0
    while pos < n:
        got = sock.recv_into(view[pos:])
        if not got:
            return None
        pos += got
    return buf


# -- asyncio side (controller) ---------------------------------------------

async def aread_msg(reader):
    # readexactly already buffers into one preallocated bytearray internally
    # (asyncio.StreamReader), so no recv_into analog is needed here.
    try:
        hdr = await reader.readexactly(4)
        (n,) = _HDR.unpack(hdr)
        data = await reader.readexactly(n)
    except (EOFError, ConnectionResetError, BrokenPipeError, OSError):
        return None
    msg = _decode(data)
    _bump_received(msg[0])
    return msg


def frame_bytes(kind: str, payload: dict, codec_on: bool = False) -> bytes:
    """Encode one framed message without writing it. Callers that fan many
    frames at the same peer in one loop step (the scheduler's dispatch pass)
    join these and hand the transport a single write — one syscall and one
    GIL release instead of one per task."""
    data = _encode(kind, payload, codec_on)
    _bump_sent(kind)
    return _HDR.pack(len(data)) + data


def awrite_msg(writer, kind: str, **payload):
    awrite_payload(writer, kind, payload)


def awrite_payload(writer, kind: str, payload: dict, codec_on: bool = False):
    data = _encode(kind, payload, codec_on)
    _bump_sent(kind)
    writer.write(_HDR.pack(len(data)) + data)
