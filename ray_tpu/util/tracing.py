"""Cluster-wide task tracing: spans with trace/span/parent ids in a
per-process bounded ring buffer.

Every process (driver, head controller thread, node agent, worker) keeps
its own ring; span context travels inside existing frames (TaskSpec
fields, task_done batch entries, node heartbeat "stats" frames) so one
``trace_id`` follows a task through

    client.submit -> controller schedule/place -> PullManager prefetch
    -> dispatch gate -> worker resolve/exec/warm -> result publish
    -> client.get

Hot-path budget: submit p50 is ~19us, so recording must stay well under
1us. That dictates the design here:

  * ``record_span`` appends ONE tuple to a deque — no dict building, no
    string formatting, no isoformat. Formatting is lazy (``events()``).
  * ids are a cached process prefix + integer counter, not uuid4.
  * ``enabled()`` is a cached module bool (re-read via ``refresh()``),
    so the disabled path is a single global load.
  * sampling (``RAY_TPU_TRACE_SAMPLE``, default 1.0) is decided ONCE at
    trace creation, deterministically from the trace id (crc32), so all
    processes agree per-trace with zero coordination. An unsampled
    submit ships ``trace_id=None`` downstream — zero cost past the
    sample check.

Timestamps: span *durations* come from monotonic-adjacent measurement at
the recording site; the stored ``ts`` is ``time.time()`` so spans from
different processes land on one comparable timeline (the Chrome trace
axis). Within one host — the loopback-cluster case — ``time.time()`` is
the same clock everywhere.

Env knobs:
  RAY_TPU_TRACE         "0" disables tracing entirely (default: on)
  RAY_TPU_TRACE_SAMPLE  fraction of traces recorded (default 1.0)
  RAY_TPU_TRACE_BUFFER  per-process ring capacity in spans (default 65536)
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
import time
import zlib
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "enabled", "sample_rate", "refresh", "new_trace_id", "new_span_id",
    "trace_id_for", "stamp", "record_span", "span", "set_current",
    "get_current", "current_trace_id", "events", "drain", "clear",
    "to_chrome", "summary", "set_process_label", "record_window",
    "ship_window", "take_shipped", "bubble_stats", "PhaseTotals", "phase",
]

_lock = threading.Lock()

_enabled: bool = True
_sample: float = 1.0
_buf: deque = deque(maxlen=65536)
_dropped: int = 0
# next(_count) is a single C-level op under the GIL — no lock on the id path
_count = itertools.count(1)
_id_prefix: str = ""
_process_label: str = ""

# per-thread current span context: (trace_id, span_id) — set by the worker
# around task execution so nested submits and log records inherit it.
# The class-level default makes `_ctx.trace` a plain attribute read on
# threads that never set a context (every driver submit): getattr with a
# default raises-and-catches AttributeError internally per call — measurable
# on the submit hot path.
class _Ctx(threading.local):
    trace: Tuple[Optional[str], Optional[int]] = (None, None)


_ctx = _Ctx()

# spans explicitly marked for shipment to the head timeline: a worker's
# ring is local-only (never drained by any heartbeat), so app code that
# wants its windows on the cluster timeline queues them here and the
# worker's next task_done frame carries them (zero extra round trips).
# Chrome-format dicts (ts/dur in µs) — the controller's timeline ring
# passes dict entries through unchanged.
_ship_outbox: List[Dict[str, Any]] = []
_SHIP_CAP = 4096


def refresh() -> None:
    """Re-read the env knobs (process start, tests, bench mode flips)."""
    global _enabled, _sample, _buf, _dropped, _id_prefix
    with _lock:
        _enabled = os.environ.get("RAY_TPU_TRACE", "1") not in ("0", "false")
        try:
            _sample = float(os.environ.get("RAY_TPU_TRACE_SAMPLE", "1.0"))
        except ValueError:
            _sample = 1.0
        try:
            cap = int(os.environ.get("RAY_TPU_TRACE_BUFFER", "65536"))
        except ValueError:
            cap = 65536
        cap = max(16, cap)
        if _buf.maxlen != cap:
            _buf = deque(_buf, maxlen=cap)
        _id_prefix = f"{os.getpid():x}-"


def trace_id_for(key: str) -> Optional[str]:
    """Sampled trace id DERIVED from an already-unique key (a task id):
    the key itself is the id, so the submit hot path neither mints nor
    stores anything — any process holding the key re-derives the same
    id AND the same sampling verdict. At the default sample rate this is
    two global loads and a compare."""
    if not _enabled:
        return None
    if _sample >= 1.0:
        return key
    if _sample <= 0.0:
        return None
    if (zlib.crc32(key.encode()) % 10000) < int(_sample * 10000):
        return key
    return None


refresh()


def enabled() -> bool:
    return _enabled


def sample_rate() -> float:
    return _sample


def set_process_label(label: str) -> None:
    """Human name for this process in Chrome traces ("driver", "node:x")."""
    global _process_label
    _process_label = label


def stamp(spec) -> Optional[str]:
    """Stamp trace context onto an outgoing TaskSpec — THE submit hot
    path, hence one cross-module call doing everything inline. The trace
    id is derived from the task id (no mint, no registry write); nested
    submits inherit the surrounding task's trace from the thread-local.
    Returns the trace id ONLY in that inherited case — the one case the
    caller must note a ref->trace mapping (a derived id needs none).

    NOTE: RemoteFunction.remote()'s fast lane inlines this body (writing
    into the spec's template dict) — keep the two in sync."""
    if not _enabled:
        return None
    tid, psid = _ctx.trace
    if tid is None:
        if _sample >= 1.0:
            spec.trace_id = spec.task_id
        elif _sample > 0.0:
            spec.trace_id = trace_id_for(spec.task_id)
        return None
    spec.trace_id = tid
    spec.parent_span_id = psid
    return tid


def new_trace_id() -> Optional[str]:
    """Mint a fresh trace id (root spans with no natural key — serve
    requests, data pipelines), or None when this trace is not sampled."""
    if not _enabled:
        return None
    return trace_id_for(_id_prefix + format(next(_count), "x"))


def new_span_id() -> int:
    return next(_count)


def set_current(trace_id: Optional[str], span_id: Optional[int]) -> None:
    _ctx.trace = (trace_id, span_id)


def get_current() -> Tuple[Optional[str], Optional[int]]:
    return _ctx.trace


def current_trace_id() -> Optional[str]:
    return _ctx.trace[0]


def record_span(name: str, cat: str, trace_id: Optional[str],
                span_id: Optional[int], parent_id: Optional[int],
                ts: float, dur: float,
                tid: Any = 0, args: Optional[dict] = None) -> None:
    """Append one completed span. ``ts`` is epoch seconds, ``dur`` seconds.

    Raw tuples only — formatting happens in ``events()``/``to_chrome()``.
    """
    global _dropped
    if not _enabled:
        return
    buf = _buf
    if len(buf) == buf.maxlen:
        _dropped += 1
        # ring overwrite is silent data loss — surface it as a counter so
        # scrapes see eviction pressure (only this degraded path pays the
        # registry lookup; get_or_create stays valid across clear_registry)
        try:
            from . import metrics
            metrics.get_or_create(
                metrics.Counter, "tracing_spans_dropped",
                "spans evicted from the trace ring before drain").inc()
        except Exception:  # noqa: BLE001 - tracing must never raise
            pass
    buf.append((name, cat, trace_id, span_id, parent_id, ts, dur, tid, args))


def record_window(name: str, cat: str, trace_id: Optional[str],
                  t0: float, t1: float, tid: Any = 0,
                  args: Optional[dict] = None,
                  parent_id: Optional[int] = None) -> None:
    """Record a span whose window was measured by the caller (epoch
    seconds). For phases whose start and end straddle awaits or callbacks
    where the ``span()`` context manager can't wrap the region — e.g. the
    PD request decomposition stamps queue / prefill / kv_ship windows
    from timestamps captured inside its pull loop."""
    if not _enabled:
        return
    record_span(name, cat, trace_id, new_span_id(), parent_id, t0,
                max(0.0, t1 - t0), tid=tid, args=args)


def ship_window(name: str, cat: str, trace_id: Optional[str],
                t0: float, t1: float, tid: Any = 0,
                args: Optional[dict] = None) -> None:
    """``record_window`` + queue the span for shipment to the head
    timeline. In a worker process the span rides the next task_done
    frame; in the driver/head process the outbox is never drained but
    the local ring (merged by DriverClient.timeline) already makes the
    span visible — the outbox is bounded, so an undrained one is
    harmless."""
    if not _enabled:
        return
    record_window(name, cat, trace_id, t0, t1, tid=tid, args=args)
    ev: Dict[str, Any] = {"name": name, "cat": cat, "ph": "X",
                          "pid": os.getpid(), "tid": tid, "ts": t0 * 1e6,
                          "dur": max(t1 - t0, 1e-6) * 1e6}
    ar = dict(args or {})
    if trace_id is not None:
        ar["trace_id"] = trace_id
    if ar:
        ev["args"] = ar
    global _dropped
    with _lock:
        if len(_ship_outbox) < _SHIP_CAP:
            _ship_outbox.append(ev)
        else:
            _dropped += 1


def take_shipped() -> List[Dict[str, Any]]:
    """Drain the ship outbox (worker task_done path): each queued span
    is forwarded exactly once."""
    with _lock:
        if not _ship_outbox:
            return []
        out = _ship_outbox[:]
        del _ship_outbox[:]
    return out


@contextmanager
def span(name: str, cat: str = "app", trace_id: Optional[str] = None,
         parent_id: Optional[int] = None, tid: Any = 0,
         args: Optional[dict] = None):
    """Context manager for non-hot paths (serve ticks, data blocks)."""
    if not _enabled:
        yield None
        return
    if trace_id is None:
        trace_id, cur = get_current()
        if parent_id is None:
            parent_id = cur
    sid = new_span_id()
    t0 = time.time()
    m0 = time.monotonic()
    try:
        yield sid
    finally:
        record_span(name, cat, trace_id, sid, parent_id, t0,
                    time.monotonic() - m0, tid=tid, args=args)


class PhaseTotals:
    """The accumulator of `phase`, owned by the caller (an engine's
    `stats()`): seconds and entries by key, every key present from
    construction at 0 so that a reader can take the delta of two snapshots.
    A phase `key` is annotated as `<prefix>.<key>`."""

    __slots__ = ("seconds", "counts", "names")

    def __init__(self, prefix: str, keys):
        self.seconds: Dict[str, float] = dict.fromkeys(keys, 0.0)
        self.counts: Dict[str, int] = dict.fromkeys(keys, 0)
        self.names: Dict[str, str] = {k: f"{prefix}.{k}" for k in keys}


# jax.profiler.TraceAnnotation, resolved once and only in a process that has
# ALREADY imported jax: the driver, the controller and the node agent import
# this module and must never load jax (on libtpu that takes the chip from the
# workers).
_annotation = None


def _trace_annotation():
    global _annotation
    if _annotation is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


class phase:
    """One stretch of a loop's host time, to two sinks: a profiler annotation
    (the ring above is on this process's own `time.time()`; a span that is to
    explain a gap on the DEVICE timeline has to sit on the profiler's clock,
    in the host plane of the same trace) and `totals.seconds[key]` /
    `totals.counts[key]` on `time.perf_counter()`. Nothing goes into the ring
    and `RAY_TPU_TRACE` is not asked: the counters are exact whatever it
    says, and with no profiler session an annotation is a flag test. Phases
    nest; all of one `totals` run on one thread, and an `await` inside one
    means the other tasks' phases are its children. `entries=0` is a further
    stretch of an entry counted where it began (a phase whose work is
    dispatched at one place of a loop and read at another)."""

    __slots__ = ("_totals", "_key", "_entries", "_ann", "_t0")

    def __init__(self, totals: PhaseTotals, key: str, entries: int = 1):
        self._totals = totals
        self._key = key
        self._entries = entries

    def __enter__(self):
        cls = _annotation or _trace_annotation()
        if cls is None:
            self._ann = None
        else:
            self._ann = cls(self._totals.names[self._key])
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, et, ev, tb):
        dt = time.perf_counter() - self._t0
        totals, key = self._totals, self._key
        totals.seconds[key] += dt
        totals.counts[key] += self._entries
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        return False


def _format(raw) -> Dict[str, Any]:
    name, cat, trace_id, span_id, parent_id, ts, dur, tid, args = raw
    d: Dict[str, Any] = {"name": name, "cat": cat, "ts": ts, "dur": dur,
                         "pid": os.getpid(), "tid": tid}
    if trace_id is not None:
        d["trace_id"] = trace_id
    if span_id is not None:
        d["span_id"] = span_id
    if parent_id is not None:
        d["parent_id"] = parent_id
    if args:
        d["args"] = dict(args)
    return d


def events() -> List[Dict[str, Any]]:
    """Formatted copy of the ring (does not clear)."""
    with _lock:
        raw = list(_buf)
    return [_format(r) for r in raw]


def drain(max_n: Optional[int] = None) -> List[Dict[str, Any]]:
    """Pop up to ``max_n`` oldest spans, formatted. Used by span shippers
    (node heartbeat) so each span is forwarded exactly once."""
    out = []
    with _lock:
        n = len(_buf) if max_n is None else min(max_n, len(_buf))
        for _ in range(n):
            out.append(_buf.popleft())
    return [_format(r) for r in out]


def clear() -> None:
    global _dropped
    with _lock:
        _buf.clear()
        del _ship_outbox[:]
        _dropped = 0
    if hasattr(_ctx, "trace"):
        _ctx.trace = (None, None)


def to_chrome(evts: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Convert formatted span dicts (ts/dur in SECONDS) to Chrome
    ``trace_event`` complete ("X") events (ts/dur in MICROSECONDS) —
    loadable in Perfetto / chrome://tracing."""
    out = []
    for e in evts:
        ev = {"name": e.get("name", "?"), "cat": e.get("cat", "app"),
              "ph": "X", "pid": e.get("pid", 1), "tid": e.get("tid", 0),
              "ts": e["ts"] * 1e6, "dur": max(e.get("dur", 0.0), 1e-6) * 1e6}
        ar = dict(e.get("args") or {})
        for k in ("trace_id", "span_id", "parent_id"):
            if k in e:
                ar[k] = e[k]
        if ar:
            ev["args"] = ar
        out.append(ev)
    if _process_label:
        out.append({"name": "process_name", "ph": "M", "pid": os.getpid(),
                    "tid": 0, "args": {"name": _process_label}})
    return out


def _merge_windows(wins: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sort + coalesce overlapping [t0, t1) intervals."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(wins):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def bubble_stats(events: List[Dict[str, Any]], phase: str = "exec",
                 name_prefix: str = "",
                 extra_cats: Tuple[str, ...] = ()) -> Dict[str, Any]:
    """Per-worker bubble fractions from a Chrome-trace event list (the
    output of ``api.timeline()`` — ts/dur in µs).

    Groups ``task_phase`` windows whose ``args.phase`` matches (default:
    the exec phase the controller stamps per task) by ``tid`` — the
    worker pid — and measures, per worker, the idle gap between its
    first window start and last window end:

        bubble_fraction = 1 - busy / span

    ``name_prefix`` filters to task names starting with it (phase events
    are named ``fn:phase``); ``extra_cats`` additionally admits whole
    events of those categories (e.g. "pipeline" for the stage-shipped
    fwd/bwd windows). This is the single implementation behind both
    ``python -m ray_tpu timeline --bubble`` and pipeline_bench's bound
    comparison — 1F1B's steady state should sit near the GPipe bound
    (S-1)/(M+S-1).
    """
    per_tid: Dict[Any, List[Tuple[float, float]]] = {}
    for e in events:
        if e.get("ph") not in (None, "X") or "ts" not in e:
            continue
        cat = e.get("cat")
        if cat == "task_phase":
            a = e.get("args") or {}
            if a.get("phase") != phase:
                continue
            if name_prefix and not str(e.get("name", "")).startswith(
                    name_prefix):
                continue
        elif cat not in extra_cats:
            continue
        t0 = e["ts"] / 1e6
        per_tid.setdefault(e.get("tid", 0), []).append(
            (t0, t0 + e.get("dur", 0.0) / 1e6))
    workers = {}
    total_busy = total_span = 0.0
    for tid, wins in sorted(per_tid.items(), key=lambda kv: str(kv[0])):
        merged = _merge_windows(wins)
        busy = sum(b - a for a, b in merged)
        span = merged[-1][1] - merged[0][0]
        bubble = max(span - busy, 0.0)
        workers[tid] = {
            "windows": len(wins), "busy_s": busy, "span_s": span,
            "bubble_s": bubble,
            "bubble_fraction": bubble / span if span > 0 else 0.0}
    total_busy = sum(w["busy_s"] for w in workers.values())
    total_span = sum(w["span_s"] for w in workers.values())
    return {"phase": phase, "workers": workers,
            "overall": {
                "busy_s": total_busy, "span_s": total_span,
                "bubble_s": max(total_span - total_busy, 0.0),
                "bubble_fraction": (1.0 - total_busy / total_span)
                                   if total_span > 0 else 0.0}}


def overlap_stats(events: List[Dict[str, Any]], name_a: str,
                  name_b: str) -> Dict[str, Any]:
    """Wall-clock overlap between two span families in a Chrome-trace
    event list (the output of ``api.timeline()`` — ts/dur in µs).

    Windows whose ``name`` starts with ``name_a`` (resp. ``name_b``) are
    merged — across ALL pids/tids, since the two families usually live in
    different processes (e.g. ``pipeline.act`` in rollout workers vs
    ``pipeline.learn`` in the driver) — and the intersection of the two
    merged interval sets is measured:

        overlap_fraction = overlap_s / min(busy_a, busy_b)

    A decoupled pipeline shows fraction near 1 (the smaller family runs
    almost entirely under the bigger one); a synchronous loop shows ~0.
    Used by ``rllib_bench`` to assert rollout/learn overlap."""
    wins: Dict[str, List[Tuple[float, float]]] = {"a": [], "b": []}
    for e in events:
        if e.get("ph") not in (None, "X") or "ts" not in e:
            continue
        name = str(e.get("name", ""))
        t0 = e["ts"] / 1e6
        w = (t0, t0 + e.get("dur", 0.0) / 1e6)
        if name.startswith(name_a):
            wins["a"].append(w)
        elif name.startswith(name_b):
            wins["b"].append(w)
    a = _merge_windows(wins["a"])
    b = _merge_windows(wins["b"])
    busy_a = sum(t1 - t0 for t0, t1 in a)
    busy_b = sum(t1 - t0 for t0, t1 in b)
    overlap = 0.0
    i = j = 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            overlap += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    floor = min(busy_a, busy_b)
    return {"windows_a": len(wins["a"]), "windows_b": len(wins["b"]),
            "busy_a_s": busy_a, "busy_b_s": busy_b, "overlap_s": overlap,
            "overlap_fraction": overlap / floor if floor > 0 else 0.0}


def summary() -> Dict[str, Any]:
    """Cheap per-process health snapshot for bench records."""
    with _lock:
        n = len(_buf)
        cats: Dict[str, int] = {}
        for r in _buf:
            cats[r[1]] = cats.get(r[1], 0) + 1
    return {"enabled": _enabled, "sample": _sample, "spans": n,
            "dropped": _dropped, "by_cat": cats}
