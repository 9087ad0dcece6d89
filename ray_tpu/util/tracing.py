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
    "enabled", "refresh", "new_trace_id", "new_span_id",
    "trace_id_for", "stamp", "record_span", "span", "set_current",
    "get_current", "current_trace_id", "events", "drain", "clear",
    "to_chrome", "summary", "record_window",
    "ship_window", "take_shipped", "bubble_stats", "PhaseTotals", "phase",
    "StallWatch", "STALL_FLOOR_S", "STALL_FACTOR", "STALL_RECENT",
    "STALL_POLL_S",
]

_lock = threading.Lock()

_enabled: bool = True
_sample: float = 1.0
_buf: deque = deque(maxlen=65536)
_dropped: int = 0
# next(_count) is a single C-level op under the GIL — no lock on the id path
_count = itertools.count(1)
_id_prefix: str = ""

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


# A watched phase is a STALL when it lasted at least STALL_FLOOR_S and more
# than STALL_FACTOR times the mean of its key's last STALL_RECENT stretches:
# what the key has taken LATELY, not since the process began. Set-up reads
# long and honestly (a compile; a first token behind a whole prompt of 8k-32k
# tokens dispatched ahead of it: 0.3-3.9 s, the device busy), and a mean that
# kept those stood at 1.5-3.6 s when the window opened (PERF.md 7 y). Inside
# a window the host runs a chunk or two ahead of its reads: the longest
# honest one in any benchmarked cell took 0.21 s, and the fewest stretches
# between set-up's last long read and the window's opening were 66, so 32
# have forgotten it twice over. The stalls this is for are 1.5-4 s; one of d
# seconds raises its key's threshold by d / 4 for the next 32 stretches.
# Constants: nobody has two values to give them.
STALL_FLOOR_S = 0.25
STALL_FACTOR = 8
STALL_RECENT = 32       # stretches of a key that its mean is taken over
STALL_POLL_S = 0.1      # the watchdog's wake-up
_STALL_ROWS = 16        # thread rows a record holds at most
_STALL_FRAMES = 4       # innermost frames a Python thread is shown by
_CLK_TCK = os.sysconf("SC_CLK_TCK")     # /proc counts CPU time in these


class PhaseTotals:
    """The accumulator of `phase`, owned by the caller (an engine's
    `stats()`): seconds and entries by key, every key present from
    construction at 0 so that a reader can take the delta of two snapshots.
    A phase `key` is annotated as `<prefix>.<key>`.

    For the keys in `watch` (phases that hold a blocking read; they do not
    nest in one another) it also counts stalls: `stall_seconds[key]`,
    `stall_counts[key]`, `stall_max_s`; `recent[key]` holds the seconds of
    the key's last STALL_RECENT stretches, stalls among them (after a change
    of what is honest the mean follows within a few entries, where a mean
    that left stalls out would call every later entry one). `open` is
    `(key, t0, serial)` of the watched phase that is open now (`t0` on
    `time.perf_counter()`), else None: one attribute, so another thread reads
    it with no lock. `stalled` holds `(key, t0, dt, limit)` of the stalled
    exits nobody has filed yet (a `StallWatch` does; with none running the
    oldest fall out)."""

    __slots__ = ("seconds", "counts", "names", "watch", "stall_seconds",
                 "stall_counts", "stall_max_s", "recent", "open", "stalled",
                 "_serial")

    def __init__(self, prefix: str, keys, watch=()):
        self.seconds: Dict[str, float] = dict.fromkeys(keys, 0.0)
        self.counts: Dict[str, int] = dict.fromkeys(keys, 0)
        self.names: Dict[str, str] = {k: f"{prefix}.{k}" for k in keys}
        self.watch = frozenset(watch)
        if not self.watch <= set(self.seconds):
            raise ValueError(f"watched keys {sorted(self.watch)} are not all "
                             f"among the keys {sorted(self.seconds)}")
        self.stall_seconds: Dict[str, float] = dict.fromkeys(watch, 0.0)
        self.stall_counts: Dict[str, int] = dict.fromkeys(watch, 0)
        self.stall_max_s = 0.0
        self.recent: Dict[str, deque] = {
            k: deque(maxlen=STALL_RECENT) for k in watch}
        self.open: Optional[Tuple[str, float, int]] = None
        self.stalled: deque = deque(maxlen=8)
        self._serial = itertools.count(1)

    def stall_threshold(self, key: str) -> Optional[float]:
        """Seconds past which an entry of `key` is a stall; None while the
        key has no entry to take a mean from (its first one compiles)."""
        recent = self.recent[key]
        if not recent:
            return None
        return max(STALL_FLOOR_S, STALL_FACTOR * sum(recent) / len(recent))


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
    dispatched at one place of a loop and read at another).

    A watched key (`PhaseTotals(watch=...)`) also shows in `totals.open`
    while it is open, and an exit that was a stall is counted and left on
    `totals.stalled`: this thread files nothing."""

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
        totals = self._totals
        self._t0 = t0 = time.perf_counter()
        if self._key in totals.watch:
            totals.open = (self._key, t0, next(totals._serial))
        return self

    def __exit__(self, et, ev, tb):
        dt = time.perf_counter() - self._t0
        totals, key = self._totals, self._key
        if key in totals.watch:
            if dt >= STALL_FLOOR_S:     # an ordinary exit stops here
                limit = totals.stall_threshold(key)
                if limit is not None and dt > limit:
                    totals.stall_seconds[key] += dt
                    totals.stall_counts[key] += 1
                    totals.stall_max_s = max(totals.stall_max_s, dt)
                    totals.stalled.append((key, self._t0, dt, limit))
            totals.recent[key].append(dt)
            totals.open = None
        totals.seconds[key] += dt
        totals.counts[key] += self._entries
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        return False


def _read(path: str) -> Optional[str]:
    try:
        with open(path) as f:
            return f.read()
    except OSError:         # no such kernel file, or the thread has ended
        return None


def _thread_sample(tid: str, first: bool) -> Optional[Dict[str, Any]]:
    """One native thread of this process as /proc shows it now: its name,
    state and CPU seconds from `stat`; run-queue seconds and context switches
    where the kernel has `schedstat` and counts switches (a sandbox's may
    not)."""
    base = f"/proc/self/task/{tid}/"
    stat = _read(base + "stat")
    if not stat:
        return None
    # the name may hold spaces and brackets: fields are counted from its end
    fields = stat[stat.rindex(")") + 2:].split()
    row = {"name": stat[stat.index("(") + 1:stat.rindex(")")],
           "state": fields[0],
           "cpu_s": (int(fields[11]) + int(fields[12])) / _CLK_TCK}
    sched = _read(base + "schedstat")
    if sched:
        row["runq_s"] = int(sched.split()[1]) / 1e9
    for ln in (_read(base + "status") or "").splitlines():
        if "ctxt_switches" in ln:       # voluntary_, nonvoluntary_
            row["invol" if ln.startswith("non") else "vol"] = int(
                ln.split()[1])
    if first:   # where the thread sleeps, while the stall is going on
        wchan = (_read(base + "wchan") or "").strip()
        if wchan not in ("", "0"):
            row["wchan"] = wchan
    return row


def _totals_of(path: str, keys) -> Dict[str, str]:
    """`key value...` lines of a /proc file, the named keys only."""
    out = {}
    for ln in (_read(path) or "").splitlines():
        key, _, rest = ln.partition(" ")
        if key.rstrip(":") in keys:
            out[key.rstrip(":")] = rest
    return out


def _short(path: str) -> str:
    """The last two parts of a source path."""
    head, tail = os.path.split(path)
    return os.path.join(os.path.basename(head), tail)


def _process_sample(first: bool) -> Dict[str, Any]:
    """What this process and its machine look like now, read from /proc and
    the interpreter alone: no jax and no call into the runtime, which may be
    what is stuck. Whatever the kernel does not show is left out. The first
    sample of a stall begins with every Python thread's innermost frames."""
    own = threading.get_native_id()
    sample: Dict[str, Any] = {"at": time.perf_counter()}
    python = {}     # native id -> name, of the Python threads
    if first:
        names = {t.ident: (t.name, t.native_id)
                 for t in threading.enumerate()}
        frames = sample["py_frames"] = {}
        for ident, frame in sys._current_frames().items():
            name, native = names.get(ident, (f"thread-{ident}", None))
            if native == own:
                continue
            rows = []
            while frame is not None and len(rows) < _STALL_FRAMES:
                code = frame.f_code
                rows.append(f"{_short(code.co_filename)}:{frame.f_lineno} "
                            f"{code.co_name}")
                frame = frame.f_back
            if name in frames:      # two threads of one name
                name = f"{name}#{native}"
            frames[name] = rows
            python[str(native)] = name
    threads = sample["threads"] = {}
    try:
        tids = os.listdir("/proc/self/task")
    except OSError:
        tids = []
    for tid in tids:
        row = None if tid == str(own) else _thread_sample(tid, first)
        if row is not None:
            if tid in python:
                row["py"] = python[tid]
            threads[tid] = row
    pressure = {}
    for what in ("cpu", "io", "memory"):
        rows = _totals_of(f"/proc/pressure/{what}", ("some", "full"))
        if rows:    # microseconds some (all) tasks stood still for it
            pressure[what] = {k: int(v.rsplit("total=", 1)[1]) / 1e6
                              for k, v in rows.items()}
    if pressure:
        sample["pressure"] = pressure
    io = _totals_of("/proc/self/io", ("read_bytes", "write_bytes"))
    if io:
        sample["proc_io"] = {k: int(v) for k, v in io.items()}
    return sample


def _delta(a: dict, b: dict) -> dict:
    """b - a, key by key and group by group, for the keys both have."""
    return {k: _delta(v, b[k]) if isinstance(v, dict) else round(b[k] - v, 6)
            for k, v in a.items() if k in b}


class StallWatch:
    """The watchdog of a `PhaseTotals` with watched keys: a daemon thread
    that lives between `start()` and `stop()` (the loop whose phases it
    watches starts it on entry and stops it in a `finally`). Every
    STALL_POLL_S it reads `totals.open`. When a watched phase has been open
    for longer than its threshold it takes a FIRST sample of the process
    (`_process_sample`, with `describe()`, the owner's plain facts); when it
    finds that entry's stalled exit on `totals.stalled` it takes a SECOND and
    hands `file` one record of the differences. A stalled exit it never
    sampled (it ended inside a poll) is filed with `sampled` False, and so is
    one whose sample failed, with `sample_error`. With no stall it is ten
    wake-ups a second that read one attribute.

    A record: `t` (`time.time()` at the phase's entry), `phase`, `dur_s`,
    `limit_s` (the threshold it passed), `sampled`, and of a sampled one
    `seen_after_s` (entry to first sample: a watchdog that could not run
    sooner was itself kept from the interpreter), `between_s` (first sample
    to second), `engine` (`describe()`), `py_frames` (every Python thread's
    innermost frames at the first sample), `pressure`, `proc_io`
    (differences) and `threads`: at most 16 rows, every native thread seen in
    state D or R at either sample first, then by CPU seconds between the
    samples."""

    def __init__(self, totals: PhaseTotals, describe, file):
        self._totals = totals
        self._describe = describe
        self._file = file
        self._done = threading.Event()
        # (entry, first sample or the error that kept it from being taken)
        self._first: Optional[Tuple[Tuple[str, float, int], Any]] = None
        self.samples = 0
        self._thread = threading.Thread(
            target=self._run, name="stall-watch", daemon=True)

    def start(self) -> "StallWatch":
        self._thread.start()
        return self

    def stop(self) -> None:
        """Ends the thread, which first files what is still on the queue."""
        self._done.set()
        self._thread.join()

    def _run(self) -> None:
        while not self._done.wait(STALL_POLL_S):
            self._look()
        self._look()

    def _look(self) -> None:
        totals = self._totals
        # a record is on the queue only once its entry has ended: whether the
        # sampled entry has closed is read from here, not from `open`, which
        # the engine's thread clears a moment after it appends
        t0 = None
        while totals.stalled:
            key, t0, dt, limit = totals.stalled.popleft()
            record = {"t": time.time() - (time.perf_counter() - t0),
                      "phase": key, "dur_s": round(dt, 6),
                      "limit_s": round(limit, 6), "sampled": False}
            if self._first is not None and self._first[0][1] == t0:
                first, self._first = self._first[1], None
                try:
                    if isinstance(first, Exception):
                        raise first
                    record.update(self._differences(
                        t0, first, _process_sample(first=False)))
                except Exception as e:  # noqa: BLE001 - the count stands
                    record["sample_error"] = repr(e)
            self._file(record)
        entry = totals.open
        if (entry is None or entry[1] == t0     # just filed, not yet cleared
                or (self._first is not None and self._first[0] == entry)):
            return
        key, t0, _ = entry
        limit = totals.stall_threshold(key)
        if limit is not None and time.perf_counter() - t0 > limit:
            try:
                sample = _process_sample(first=True)
                sample["engine"] = self._describe()
            except Exception as e:  # noqa: BLE001 - a /proc line, describe()
                sample = e
            self._first = (entry, sample)
            self.samples += 1

    @staticmethod
    def _differences(t0: float, a: dict, b: dict) -> Dict[str, Any]:
        rows = []
        for tid, x in a["threads"].items():
            y = b["threads"].get(tid)
            if y is None:
                continue
            row = {"tid": int(tid), "name": x["name"],
                   "state": x["state"] + y["state"],
                   **_delta({k: x[k] for k in ("cpu_s", "runq_s", "vol",
                                               "invol") if k in x}, y),
                   **{k: x[k] for k in ("py", "wchan") if k in x}}
            rows.append(row)
        rows.sort(key=lambda r: (not set(r["state"]) & {"D", "R"},
                                 -r["cpu_s"], -r.get("runq_s", 0.0),
                                 "py" not in r))
        out = {"sampled": True, "seen_after_s": round(a["at"] - t0, 6),
               "between_s": round(b["at"] - a["at"], 6),
               "engine": a["engine"], "py_frames": a["py_frames"],
               "n_threads": len(a["threads"]), "threads": rows[:_STALL_ROWS]}
        for group in ("pressure", "proc_io"):
            if group in a and group in b:
                out[group] = _delta(a[group], b[group])
        return out


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
