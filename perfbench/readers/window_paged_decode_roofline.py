"""`paged_decode_roofline.py` for a model with sliding-window layers: the
decode kernels' share (%) of their roofline in the traced seconds, both names
(`paged_decode` of the full layers, `paged_decode_window` of the sliding
ones). The least time is the bytes of the keys and values the rows' queries
can SEE (`counts/window_cache.py`: min(len, window) in the sliding layers,
len in the full ones, as the engine summed them over rows and steps at the
syncs inside the traced seconds) over the chip's bandwidth. None on a program
without such layers."""

from perfbench import trace_reduce
from perfbench.counts import window_cache


def traced(run: dict, key: str) -> list:
    """The entries of stats()["window"][key] ((time, a, b) each) inside the
    traced interval; None where the program keeps no such list."""
    trace = run.get("trace")
    recent = run["counters"]["close"]["stats"].get("window", {}).get(key)
    if not trace or recent is None:
        return None
    return [r for r in recent if trace["t0"] <= r[0] <= trace["t1"]]


def read(run: dict, args: dict):
    syncs = traced(run, "recent_decode_syncs")
    if not syncs or not run.get("peaks"):
        return None
    _, seconds = trace_reduce.seconds_of(run["trace"]["ops"], "paged_decode")
    if not seconds:
        return None
    needed = window_cache.paged_decode_bytes(
        sum(r[1] for r in syncs), sum(r[2] for r in syncs), run["sizes"])
    return 100.0 * needed / run["peaks"]["hbm_bytes_per_s"] / seconds
