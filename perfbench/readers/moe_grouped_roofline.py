"""The grouped expert product's share (%) of its roofline in the decode steps
of the traced seconds: the least time of a layer's call (`counts/moe_grouped.py`:
the weights of the experts its rows reach over peak bandwidth, or its
operations over the peak rate if that is longer), times the layer calls the
trace holds, over the device time of the product's kernels in the decode
programs (args: ops, needle groups as `ops_time_share.py` takes them;
kernels_per_call, how many of them one layer's call runs: the calls are counted
from the same kernels whose time is the denominator). How many experts a call
reaches only the device knows, and it moves with what the streams are saying:
the engine counts them at each decode sync (`stats()["moe"]
["recent_decode_syncs"]`: time.monotonic(), layer calls, experts touched), and
the syncs inside the traced seconds give the mean (routing follows the tokens,
and a greedy stream on seeded weights reaches far fewer experts than rows
spread evenly would). A step computes every slot's row, so the rows are the
engine's slots. None on a program without the grouped product."""

from perfbench.counts import moe_grouped
from perfbench.readers import ops_match


def read(run: dict, args: dict):
    trace = run.get("trace")
    recent = run["counters"]["close"]["stats"].get("moe", {}).get(
        "recent_decode_syncs")
    if not trace or not run.get("peaks") or not recent:
        return None
    inside = [r for r in recent if trace["t0"] <= r[0] <= trace["t1"]]
    calls = sum(r[1] for r in inside)
    count, seconds = ops_match.seconds_of(trace["ops"], args["ops"])
    if not seconds or calls <= 0:
        return None
    touched = sum(r[2] for r in inside) / calls
    least = moe_grouped.least_seconds(run["counters"]["close"]["slots"],
                                      run["sizes"], run["peaks"], touched)
    return 100.0 * count / args["kernels_per_call"] * least / seconds
