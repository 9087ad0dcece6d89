"""Frame codec: packed fixed-layout encoding for high-frequency control
frames (src/frame_codec.cpp holds the native scanner; this module owns the
layouts).

The pipelined control plane ships almost all hot traffic as multi-entry
"batch" frames (client._DeltaFlusher -> controller._apply_batch): put
registrations, refcount deltas, task_done publications and pipelined
submits. This codec packs those frames as fixed-layout structs instead of
pickle:

  frame: u8 magic 0xC3 | u8 version 1 | u8 kind (1=batch, 2=exec) |
         u32 nentries | entry*
  entry: u8 opcode | u32 body_len | body

Kind 2 ("exec") is the scheduler's dispatch frame — exactly one OP_EXEC
entry carrying the TaskSpec, result oids and prefetched arg descriptors —
so the per-dispatch hot path skips pickle too (controller._dispatch sends
it codec-coded once the worker negotiated codec_ver > 0).

Pickle frames always begin 0x80 (protocol >= 2), so receivers sniff the
first byte — protocol.recv_msg/aread_msg route 0xC3 frames here and
everything else through pickle. Encoding is opportunistic: any entry the
fixed layouts can't express (exotic TaskSpec field types, oversized ids)
makes `encode` return None and the sender falls back to pickle for that
frame. Rare frame kinds (RPCs, replies, heartbeats) never come here.

Refcount runs get a special entry: consecutive incref/decref entries on
"obj-" ids pack into ONE "refdeltas" body whose byte layout is exactly what
the sharded directory's bulk od_apply_deltas consumes — the controller
hands the decoded body straight to the directory without materializing
per-id Python tuples (the decref-storm path).

Negotiation: register/register_node handshakes carry `codec_ver`; each side
uses min(its own wire_version(), the peer's). `RAY_TPU_NATIVE=0` forces
wire_version() to 0 — the all-pickle escape hatch (README, control plane).

Both implementations of the scan — the native fc_scan and the pure-Python
loop — produce/consume identical bytes; the golden tests pin the format
byte-for-byte against both.
"""

import ctypes
import os
import pickle
import struct
import threading
from typing import List, Optional, Tuple

from . import build, objdir

MAGIC = 0xC3
VERSION = 1
KIND_BATCH = 1
KIND_EXEC = 2   # dispatch frame: exactly one OP_EXEC entry

OP_REFDELTAS = 1
OP_PUT = 2
OP_ACTOR_INCREF = 3
OP_ACTOR_DECREF = 4
OP_OPEN_STREAM = 5
OP_CLOSE_STREAM = 6
OP_TASK_DONE = 7
OP_SUBMIT = 8
OP_INCREF_ONE = 9
OP_DECREF_ONE = 10
OP_EXEC = 11    # kind-2 frames only (batch frames stop at 10)

_HDR = struct.Struct("<BBBI")   # magic, version, kind, nentries
_ENT = struct.Struct("<BI")     # opcode, body_len
_U16 = struct.Struct("<H")
_U32 = struct.Struct("<I")

_lock = threading.Lock()
_lib = None
_build_error: Optional[str] = None


def _compile() -> str:
    return build("frame_codec")


def _load():
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        try:
            lib = ctypes.CDLL(_compile())
        except Exception as e:  # noqa: BLE001 - fall back to the Python scan
            _build_error = str(e)
            return None
        lib.fc_version.restype = ctypes.c_int32
        lib.fc_validate.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.fc_validate.restype = ctypes.c_int64
        lib.fc_scan.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                ctypes.POINTER(ctypes.c_int64),
                                ctypes.c_int64]
        lib.fc_scan.restype = ctypes.c_int64
        lib.fc_validate_deltas.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.fc_validate_deltas.restype = ctypes.c_int64
        _lib = lib
        return _lib


def native_disabled() -> bool:
    return os.environ.get("RAY_TPU_NATIVE", "").lower() in ("0", "false", "no")


def native_available() -> bool:
    """True when the C scanner builds/loads (the wire format itself needs no
    toolchain — the Python scan speaks it identically)."""
    return _load() is not None


def wire_version() -> int:
    """Codec version this process is willing to speak on the wire. 0 means
    all-pickle (the RAY_TPU_NATIVE=0 escape hatch)."""
    return 0 if native_disabled() else VERSION


def negotiate(peer_ver) -> int:
    """Per-connection version: the min of both sides' wire_version()."""
    try:
        return min(wire_version(), int(peer_ver or 0))
    except (TypeError, ValueError):
        return 0


# ---------------------------------------------------------------- primitives

def _pstr(parts: list, s: str):
    raw = s.encode()
    if len(raw) > 0xFFFF:
        raise ValueError("string too long for u16 frame field")
    parts.append(_U16.pack(len(raw)))
    parts.append(raw)


def _pbytes_opt(parts: list, b):
    if b is None:
        parts.append(b"\x00")
    else:
        b = bytes(b)
        parts.append(b"\x01")
        parts.append(_U32.pack(len(b)))
        parts.append(b)


def _gstr(mv, pos: int) -> Tuple[str, int]:
    (n,) = _U16.unpack_from(mv, pos)
    pos += 2
    return bytes(mv[pos:pos + n]).decode(), pos + n


def _gbytes_opt(mv, pos: int):
    if mv[pos] == 0:
        return None, pos + 1
    (n,) = _U32.unpack_from(mv, pos + 1)
    pos += 5
    return bytes(mv[pos:pos + n]), pos + n


# ------------------------------------------------------------- entry bodies

def _enc_putlike(parts: list, oid, meta_len, size, inline, contained):
    """Shared body for put entries and task_done result tuples:
    str oid | u32 meta_len | u64 size | bytes? inline | u16 n | str* contained."""
    _pstr(parts, oid)
    parts.append(struct.pack("<IQ", meta_len, size))
    _pbytes_opt(parts, inline)
    contained = contained or []
    parts.append(_U16.pack(len(contained)))
    for c in contained:
        _pstr(parts, c)


def _dec_putlike(mv, pos: int):
    oid, pos = _gstr(mv, pos)
    meta_len, size = struct.unpack_from("<IQ", mv, pos)
    pos += 12
    inline, pos = _gbytes_opt(mv, pos)
    (n,) = _U16.unpack_from(mv, pos)
    pos += 2
    contained = []
    for _ in range(n):
        c, pos = _gstr(mv, pos)
        contained.append(c)
    return (oid, meta_len, size, inline, contained), pos


def _enc_spec(parts: list, spec) -> None:
    """TaskSpec fixed layout + a pickled `extras` dict for the rare fields.
    Raises on anything the layout can't express (caller falls back)."""
    _pstr(parts, spec.task_id)
    _pbytes_opt(parts, spec.fn_blob)
    args = spec.args or []
    parts.append(_U16.pack(len(args)))
    for kind, v in args:
        _enc_arg(parts, kind, v)
    kwargs = spec.kwargs or {}
    parts.append(_U16.pack(len(kwargs)))
    for k, (kind, v) in kwargs.items():
        _pstr(parts, k)
        _enc_arg(parts, kind, v)
    if spec.num_returns == "streaming":
        parts.append(b"\x01")
    else:
        parts.append(b"\x00" + struct.pack("<i", int(spec.num_returns)))
    res = spec.resources or {}
    if len(res) > 0xFF:
        raise ValueError("too many resource kinds")
    parts.append(struct.pack("<B", len(res)))
    for k, v in res.items():
        _pstr(parts, k)
        parts.append(struct.pack("<d", float(v)))
    if type(spec.retry_exceptions) is not bool:
        raise ValueError("non-bool retry_exceptions")  # rare: pickle path
    parts.append(struct.pack("<iB", int(spec.max_retries),
                             1 if spec.retry_exceptions else 0))
    _pstr(parts, spec.name or "")
    extras = {}
    for f, default in _SPEC_EXTRAS:
        v = getattr(spec, f)
        if v != default:
            extras[f] = v
    _pbytes_opt(parts, pickle.dumps(extras, protocol=5) if extras else None)


def _enc_arg(parts: list, kind, v):
    if kind == "v":
        b = bytes(v)
        parts.append(b"\x00" + _U32.pack(len(b)))
        parts.append(b)
    elif kind == "ref":
        parts.append(b"\x01")
        _pstr(parts, v)
    else:
        raise ValueError(f"unknown arg kind {kind!r}")


def _dec_arg(mv, pos: int):
    tag = mv[pos]
    pos += 1
    if tag == 0:
        (n,) = _U32.unpack_from(mv, pos)
        pos += 4
        return ("v", bytes(mv[pos:pos + n])), pos + n
    oid, pos = _gstr(mv, pos)
    return ("ref", oid), pos


# TaskSpec fields outside the fixed layout, shipped as a pickled dict only
# when they differ from their defaults (plain tasks pay ~1 byte).
_SPEC_EXTRAS = (
    ("actor_id", None), ("method_name", None), ("is_actor_creation", False),
    ("scheduling_strategy", None), ("placement_group_id", None),
    ("placement_group_bundle_index", -1), ("runtime_env", None),
    ("generator_backpressure", 0), ("parent_task_id", None), ("job_id", None),
    ("trace_id", None), ("parent_span_id", None), ("nested_refs", []),
    ("owner_id", None), ("owned_inline", None),
)


def _dec_spec(mv, pos: int):
    from ray_tpu._private.task_spec import TaskSpec
    task_id, pos = _gstr(mv, pos)
    fn_blob, pos = _gbytes_opt(mv, pos)
    (nargs,) = _U16.unpack_from(mv, pos)
    pos += 2
    args = []
    for _ in range(nargs):
        a, pos = _dec_arg(mv, pos)
        args.append(a)
    (nkw,) = _U16.unpack_from(mv, pos)
    pos += 2
    kwargs = {}
    for _ in range(nkw):
        k, pos = _gstr(mv, pos)
        a, pos = _dec_arg(mv, pos)
        kwargs[k] = a
    if mv[pos] == 1:
        num_returns = "streaming"
        pos += 1
    else:
        (num_returns,) = struct.unpack_from("<i", mv, pos + 1)
        pos += 5
    nres = mv[pos]
    pos += 1
    resources = {}
    for _ in range(nres):
        k, pos = _gstr(mv, pos)
        (v,) = struct.unpack_from("<d", mv, pos)
        pos += 8
        resources[k] = v
    max_retries, retry_exc = struct.unpack_from("<iB", mv, pos)
    pos += 5
    name, pos = _gstr(mv, pos)
    extras_blob, pos = _gbytes_opt(mv, pos)
    spec = TaskSpec(task_id=task_id, fn_blob=fn_blob, args=args, kwargs=kwargs,
                    num_returns=num_returns, resources=resources,
                    max_retries=max_retries, retry_exceptions=bool(retry_exc),
                    name=name)
    if extras_blob:
        for k, v in pickle.loads(extras_blob).items():
            setattr(spec, k, v)
    return spec, pos


def _enc_exec(parts: list, payload: dict) -> None:
    """Exec-frame body: spec | u16 n | str* result_oids | u8 has_descs |
    [u16 n | (str oid | u8 tag | inline bytes / u32 shm meta_len)*].
    Raises on desc kinds outside inline/shm (caller falls back to pickle)."""
    _enc_spec(parts, payload["spec"])
    oids = payload["result_oids"]
    parts.append(_U16.pack(len(oids)))
    for oid in oids:
        _pstr(parts, oid)
    descs = payload.get("arg_descs")
    if descs is None:
        parts.append(b"\x00")
        return
    parts.append(b"\x01")
    parts.append(_U16.pack(len(descs)))
    for oid, (kind, v) in descs.items():
        _pstr(parts, oid)
        if kind == "inline":
            b = bytes(v)
            parts.append(b"\x00" + _U32.pack(len(b)))
            parts.append(b)
        elif kind == "shm":
            parts.append(b"\x01" + _U32.pack(int(v)))
        else:
            raise ValueError(f"no exec layout for desc kind {kind!r}")


def _dec_exec(mv) -> dict:
    spec, pos = _dec_spec(mv, 0)
    (n,) = _U16.unpack_from(mv, pos)
    pos += 2
    oids = []
    for _ in range(n):
        oid, pos = _gstr(mv, pos)
        oids.append(oid)
    out = {"spec": spec, "result_oids": oids}
    has_descs = mv[pos]
    pos += 1
    if has_descs:
        (nd,) = _U16.unpack_from(mv, pos)
        pos += 2
        descs = {}
        for _ in range(nd):
            oid, pos = _gstr(mv, pos)
            tag = mv[pos]
            pos += 1
            if tag == 0:
                (ln,) = _U32.unpack_from(mv, pos)
                pos += 4
                descs[oid] = ("inline", bytes(mv[pos:pos + ln]))
                pos += ln
            else:
                (ml,) = _U32.unpack_from(mv, pos)
                pos += 4
                descs[oid] = ("shm", ml)
        out["arg_descs"] = descs
    return out


def _enc_entry(e) -> Tuple[int, bytes]:
    op = e[0]
    parts: list = []
    if op == "put":
        _enc_putlike(parts, e[1], e[2], e[3], e[4], e[5])
        return OP_PUT, b"".join(parts)
    if op == "task_done":
        _pstr(parts, e[1])
        results = e[2] or []
        parts.append(_U16.pack(len(results)))
        for r in results:
            _enc_putlike(parts, r[0], r[1], r[2], r[3],
                         r[4] if len(r) > 4 else None)
        error = e[3]
        _pbytes_opt(parts, pickle.dumps(error, protocol=5)
                    if error is not None else None)
        span = e[4] if len(e) > 4 else None
        _pbytes_opt(parts, pickle.dumps(span, protocol=5)
                    if span is not None else None)
        spans = e[5] if len(e) > 5 else None
        _pbytes_opt(parts, pickle.dumps(spans, protocol=5)
                    if spans else None)
        return OP_TASK_DONE, b"".join(parts)
    if op == "submit":
        _enc_spec(parts, e[1])
        oids = e[2]
        parts.append(_U16.pack(len(oids)))
        for oid in oids:
            _pstr(parts, oid)
        return OP_SUBMIT, b"".join(parts)
    if op == "refdeltas":
        return OP_REFDELTAS, bytes(e[1])
    single = {"actor_incref": OP_ACTOR_INCREF, "actor_decref": OP_ACTOR_DECREF,
              "open_stream": OP_OPEN_STREAM, "close_stream": OP_CLOSE_STREAM,
              "incref": OP_INCREF_ONE, "decref": OP_DECREF_ONE}.get(op)
    if single is None:
        raise ValueError(f"no fixed layout for batch entry {op!r}")
    _pstr(parts, e[1])
    return single, b"".join(parts)


def _dec_entry(opcode: int, body):
    mv = memoryview(body)
    if opcode == OP_REFDELTAS:
        return ("refdeltas", bytes(mv))
    if opcode == OP_PUT:
        (oid, meta_len, size, inline, contained), _ = _dec_putlike(mv, 0)
        return ("put", oid, meta_len, size, inline, contained)
    if opcode == OP_TASK_DONE:
        task_id, pos = _gstr(mv, 0)
        (n,) = _U16.unpack_from(mv, pos)
        pos += 2
        results = []
        for _ in range(n):
            r, pos = _dec_putlike(mv, pos)
            results.append(r)
        err_blob, pos = _gbytes_opt(mv, pos)
        span_blob, pos = _gbytes_opt(mv, pos)
        spans_blob, pos = _gbytes_opt(mv, pos)
        return ("task_done", task_id, results,
                pickle.loads(err_blob) if err_blob else None,
                pickle.loads(span_blob) if span_blob else None,
                pickle.loads(spans_blob) if spans_blob else None)
    if opcode == OP_SUBMIT:
        spec, pos = _dec_spec(mv, 0)
        (n,) = _U16.unpack_from(mv, pos)
        pos += 2
        oids = []
        for _ in range(n):
            oid, pos = _gstr(mv, pos)
            oids.append(oid)
        return ("submit", spec, oids)
    name = {OP_ACTOR_INCREF: "actor_incref", OP_ACTOR_DECREF: "actor_decref",
            OP_OPEN_STREAM: "open_stream", OP_CLOSE_STREAM: "close_stream",
            OP_INCREF_ONE: "incref", OP_DECREF_ONE: "decref"}[opcode]
    sid, _ = _gstr(mv, 0)
    return (name, sid)


# ----------------------------------------------------------------- frame API

def fold_refdeltas(entries):
    """Collapse consecutive incref/decref entries on plain object ids into
    packed ("refdeltas", bytes) entries — order among entries is preserved,
    so put-before-decref still holds. Used by the wire encoder AND by the
    driver's local batch post, so the controller's bulk directory path runs
    for both transports."""
    out = []
    run = []
    for e in entries:
        op = e[0]
        if op in ("incref", "decref") and e[1].startswith("obj-"):
            run.append((objdir.INCREF if op == "incref" else objdir.DECREF,
                        e[1]))
            continue
        if run:
            out.append(("refdeltas", objdir.pack_deltas(run)))
            run = []
        out.append(e)
    if run:
        out.append(("refdeltas", objdir.pack_deltas(run)))
    return out


def encode(kind: str, payload: dict) -> Optional[bytes]:
    """Encode a frame, or None when `kind`/payload has no fixed layout (the
    sender then pickles — the negotiated fallback)."""
    if kind == "exec":
        if not ({"spec", "result_oids"} <= set(payload)
                <= {"spec", "result_oids", "arg_descs"}):
            return None
        try:
            body_parts: list = []
            _enc_exec(body_parts, payload)
            body = b"".join(body_parts)
            return b"".join([_HDR.pack(MAGIC, VERSION, KIND_EXEC, 1),
                             _ENT.pack(OP_EXEC, len(body)), body])
        except Exception:  # noqa: BLE001 - opportunistic: odd specs pickle
            return None
    if kind != "batch" or set(payload) != {"entries"}:
        return None
    try:
        entries = fold_refdeltas(payload["entries"])
        parts = [_HDR.pack(MAGIC, VERSION, KIND_BATCH, len(entries))]
        for e in entries:
            opcode, body = _enc_entry(e)
            parts.append(_ENT.pack(opcode, len(body)))
            parts.append(body)
        return b"".join(parts)
    except Exception:  # noqa: BLE001 - opportunistic: odd payloads pickle
        return None


def _scan_py(data) -> List[Tuple[int, int, int]]:
    mv = memoryview(data)
    if len(mv) < 7 or mv[0] != MAGIC:
        raise ValueError("not a codec frame")
    if mv[1] != VERSION:
        raise ValueError(f"unsupported codec version {mv[1]}")
    kind = mv[2]
    if kind not in (KIND_BATCH, KIND_EXEC):
        raise ValueError(f"unknown codec frame kind {kind}")
    (n,) = _U32.unpack_from(mv, 3)
    if kind == KIND_EXEC and n != 1:
        raise ValueError("malformed codec frame")
    pos = 7
    out = []
    for _ in range(n):
        if pos + 5 > len(mv):
            raise ValueError("malformed codec frame")
        opcode, blen = _ENT.unpack_from(mv, pos)
        pos += 5
        op_ok = (1 <= opcode <= OP_DECREF_ONE if kind == KIND_BATCH
                 else opcode == OP_EXEC)
        if not op_ok or pos + blen > len(mv):
            raise ValueError("malformed codec frame")
        out.append((opcode, pos, blen))
        pos += blen
    if pos != len(mv):
        raise ValueError("malformed codec frame")
    return out


def _scan_native(lib, data) -> List[Tuple[int, int, int]]:
    if len(data) < 7 or data[0] != MAGIC:
        raise ValueError("not a codec frame")
    (n,) = _U32.unpack_from(data, 3)
    # bound the result allocation by what the frame could possibly hold
    # (>=5 bytes per entry) BEFORE trusting n — a lying header must not
    # drive a multi-GB ctypes array
    if n > (len(data) - 7) // 5:
        raise ValueError("malformed codec frame")
    arr = (ctypes.c_int64 * (3 * max(n, 1)))()
    r = lib.fc_scan(bytes(data), len(data), arr, n)
    if r < 0:
        raise ValueError(f"malformed codec frame (fc_scan {r})")
    return [(arr[i * 3], arr[i * 3 + 1], arr[i * 3 + 2]) for i in range(r)]


def decode(data):
    """Decode a 0xC3 frame into the same (kind, payload) shape pickle
    produces. Works with or without the native scanner (RAY_TPU_NATIVE=0
    disables the C library but a peer may still be mid-handshake — decoding
    stays available so no frame is ever dropped)."""
    data = bytes(data)
    lib = None if native_disabled() else _load()
    items = _scan_native(lib, data) if lib is not None else _scan_py(data)
    mv = memoryview(data)
    if data[2] == KIND_EXEC:
        op, off, ln = items[0]
        return ("exec", _dec_exec(mv[off:off + ln]))
    entries = [_dec_entry(op, mv[off:off + ln]) for op, off, ln in items]
    return ("batch", {"entries": entries})


def is_codec_frame(data) -> bool:
    return len(data) > 0 and data[0] == MAGIC
