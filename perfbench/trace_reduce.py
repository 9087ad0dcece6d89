"""Reduction of a jax profiler trace (`.xplane.pb`) to what the per-layer
readers and the result line need. It runs in the process that made the trace
(the parent has no jax), reads the file with `jax.profiler.ProfileData` alone,
and returns plain numbers and short tables.

What it reads, as a TPU trace has it: one plane per device
(`/device:TPU:<n>`), whose line `XLA Ops` holds one event per executed HLO
operation (nested for `while`, `conditional` and `call`) and whose line
`XLA Modules` holds one event per executed program; and the plane `/host:CPU`
with one line per host thread of TraceMe events (`PjitFunction(decode_chunk)`,
`np.asarray(jax.Array)`, ...). On the CPU backend (the rehearsal and the
recorded test trace) the operations sit on the host plane's `tf_XLA...` lines
with an `hlo_op` stat; they are read as one device.

- busy_s: union of the intervals in which an operation ran on a device,
  averaged over the devices; window_s: first operation's start to the last
  one's end over all devices.
- ops / modules: seconds and counts by name, summed over devices. An
  operation's name is its HLO name without the numeric suffix, followed by the
  result's type and dimensions where the trace carries them (a TPU trace names
  an operation by its whole HLO text), as `copy_bf16_16_8_1152_64_128_`, so
  that one name is one kind of work.
- collective_s: seconds of collective operations on the operation line of a
  device, averaged over devices. The line is serial with compute, so this is
  time in which no compute ran there: the exposed part. What the compiler
  hid under compute does not appear on that line.
- idle_gaps: the longest gaps between device operations, by the host event
  that covers most of each gap.
"""

import glob
import os
import re

import numpy as np

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_CONTAINERS = ("while", "conditional", "call")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
                "collective-permute", "all-to-all", "collective-broadcast")
_MAX_GAPS = 2000      # gaps attributed to host events, longest first
_TABLE = 400          # names kept per table


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


_HLO = re.compile(r"^%?([^\s=]+?)(?:\.\d+)*\s*=\s*\(?\s*([a-z]+[0-9]*)\[([0-9,]*)\]")


def op_name(event) -> str:
    """One name for one kind of work. A TPU trace names an operation by its
    HLO text, `%fusion.123 = bf16[32,14336]{1,0:T(8,128)} fusion(...)`: that
    becomes `fusion_bf16_32_14336_` (name without its number, the result's
    type and dimensions; of a tuple, the first element's). A bare name
    (`fusion.123`, the CPU backend) loses its number only."""
    m = _HLO.match(event.name)
    if m:
        return f"{m.group(1)}_{m.group(2)}_{m.group(3).replace(',', '_')}_"
    return re.sub(r"(\.\d+)+$", "", event.name.lstrip("%")) or event.name


def _busy_runs(starts, ends):
    """The maximal runs [(start, end)] in which some interval is open."""
    order = np.argsort(starts)
    starts, ends = starts[order], np.maximum.accumulate(ends[order])
    # a new run begins where a start lies beyond every earlier end
    new = np.concatenate([[True], starts[1:] > ends[:-1]])
    return starts[new], np.concatenate([ends[:-1][new[1:]], ends[-1:]])


def _union_seconds(starts, ends) -> float:
    """Total length of the union of [start, end) intervals (ns -> s)."""
    if len(starts) == 0:
        return 0.0
    run_starts, run_ends = _busy_runs(starts, ends)
    return float(np.sum(run_ends - run_starts)) / 1e9


def _gaps(starts, ends):
    """Idle gaps (starts, ends) between the busy runs of one device."""
    run_starts, run_ends = _busy_runs(starts, ends)
    return run_ends[:-1], run_starts[1:]


def _device_lines(data):
    """[(ops events, module events)] per device."""
    out = []
    for plane in data.planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        ops = lines.get("XLA Ops")
        if ops is None:
            continue
        mods = lines.get("XLA Modules")
        out.append((list(ops.events), list(mods.events) if mods else []))
    if out:
        return out
    # CPU backend: operations carry an `hlo_op` stat on host thread lines
    ops = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            if ln.name.startswith("tf_XLA"):
                ops += [e for e in ln.events
                        if any(k == "hlo_op" for k, _ in e.stats)]
    return [(ops, [])] if ops else []


def _host_events(data):
    names, starts, ends = [], [], []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            if ln.name.startswith("tf_"):     # runtime pools, not the program
                continue
            for e in ln.events:
                if e.duration_ns > 0:
                    names.append(e.name)
                    starts.append(e.start_ns)
                    ends.append(e.start_ns + e.duration_ns)
    return names, np.asarray(starts, np.float64), np.asarray(ends, np.float64)


def _table(totals: dict) -> dict:
    top = sorted(totals.items(), key=lambda kv: -kv[1][1])[:_TABLE]
    return {k: [int(c), float(s)] for k, (c, s) in top}


def _clean(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.:-]", "_", name)[:80]


def reduce(data) -> dict:
    devices = _device_lines(data)
    if not devices:
        return {"devices": 0, "busy_s": 0.0, "window_s": 0.0, "ops": {},
                "modules": {}, "collective_s": 0.0, "device_ops": [],
                "idle_gaps": []}
    ops, modules = {}, {}
    busy = collective = 0.0
    first, last = np.inf, -np.inf
    gap_starts, gap_ends = [], []
    for op_events, mod_events in devices:
        starts = np.asarray([e.start_ns for e in op_events], np.float64)
        ends = starts + np.asarray([e.duration_ns for e in op_events],
                                   np.float64)
        busy += _union_seconds(starts, ends)
        if len(starts):
            first, last = min(first, starts.min()), max(last, ends.max())
            g0, g1 = _gaps(starts, ends)
            gap_starts.append(g0)
            gap_ends.append(g1)
        for e in op_events:
            raw = e.name.lstrip("%")
            if raw.startswith(_CONTAINERS):
                continue
            if raw.startswith(_COLLECTIVES):
                collective += e.duration_ns / 1e9
            row = ops.setdefault(op_name(e), [0, 0.0])
            row[0] += 1
            row[1] += e.duration_ns / 1e9
        for e in mod_events:
            row = modules.setdefault(re.sub(r"\(\d+\)$", "", e.name), [0, 0.0])
            row[0] += 1
            row[1] += e.duration_ns / 1e9
    n = len(devices)
    idle = {}
    if gap_starts:
        g0, g1 = np.concatenate(gap_starts), np.concatenate(gap_ends)
        longest = np.argsort(g0 - g1)[:_MAX_GAPS]
        names, h0, h1 = _host_events(data)
        for i in longest:
            label = "host:_no_traced_event"
            if len(h0):
                overlap = np.minimum(h1, g1[i]) - np.maximum(h0, g0[i])
                best = overlap.max()
                if best > 0:   # of the events that cover most, the innermost
                    cand = np.flatnonzero(overlap >= 0.999 * best)
                    label = names[cand[np.argmin((h1 - h0)[cand])]]
            idle[label] = idle.get(label, 0.0) + (g1[i] - g0[i]) / 1e9 / n
    ops_table = _table(ops)
    return {
        "devices": n,
        "busy_s": busy / n,
        "window_s": float(last - first) / 1e9,
        "ops": ops_table,
        "modules": _table(modules),
        "collective_s": collective / n,
        "device_ops": [[_clean(k), v[1] / n]
                       for k, v in list(ops_table.items())[:10]],
        "idle_gaps": [[_clean(k), float(v)] for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:10]],
    }


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path))


def seconds_of(table: dict, *needles: str):
    """(count, seconds) of the table's names that contain any needle."""
    count, seconds = 0, 0.0
    for name, (c, s) in table.items():
        if any(n in name for n in needles):
            count, seconds = count + c, seconds + s
    return count, seconds
