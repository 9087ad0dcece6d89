"""The KDA decode update's share (%) of its roofline in the traced seconds. It
is HBM-bound: the least time is each active row's state read and written once
a linear layer a step (`counts/kda.py`) over the peak bandwidth; the share is
that over the device time of the update's operations (args: ops, needle groups
as `ops_time_share.py` takes them). Rows and steps are the deployment's
samples at each sync inside the traced interval. None on a program without
linear-attention layers."""

from perfbench.counts import kda
from perfbench.readers import ops_match


def read(run: dict, args: dict):
    trace, sizes = run.get("trace"), run["sizes"]
    if not trace or not run.get("peaks") or not sizes.get("full_attn_every"):
        return None
    _, seconds = ops_match.seconds_of(trace["ops"], args["ops"])
    syncs = ops_match.traced_syncs(run)
    if not seconds or not syncs:
        return None
    needed = sum(kda.decode_bytes((s[3] or 1) * s[1], sizes) for s in syncs)
    return 100.0 * needed / run["peaks"]["hbm_bytes_per_s"] / seconds
