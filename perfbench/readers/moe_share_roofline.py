"""`moe_grouped_roofline.py` for a bank that holds a share of the experts: the
grouped product's share (%) of its roofline in the decode steps of the traced
seconds, on `counts/moe_share.py` (the pairs that fall on a held expert, one in
n_experts / experts_held of the slots' rows x top_k, and the weights of the
held experts they reach as the engine counted them at the syncs inside the
traced seconds). args as `moe_grouped_roofline.py`'s. None on a program whose
bank holds every expert."""

from perfbench.counts import moe_share
from perfbench.readers import ops_match


def read(run: dict, args: dict):
    trace, sizes = run.get("trace"), run["sizes"]
    recent = run["counters"]["close"]["stats"].get("moe", {}).get(
        "recent_decode_syncs")
    if (not trace or not run.get("peaks") or not recent
            or not sizes.get("experts_held")):
        return None
    inside = [r for r in recent if trace["t0"] <= r[0] <= trace["t1"]]
    calls = sum(r[1] for r in inside)
    count, seconds = ops_match.seconds_of(trace["ops"], args["ops"])
    if not seconds or calls <= 0:
        return None
    touched = sum(r[2] for r in inside) / calls
    pairs = moe_share.held_pairs(run["counters"]["close"]["slots"], sizes)
    least = moe_share.least_seconds(pairs, sizes, run["peaks"], touched)
    return 100.0 * count / args["kernels_per_call"] * least / seconds
