"""The SSD decode update's share (%) of its roofline in the traced seconds. It
is HBM-bound: the least time is each LIVE row's state read and written once a
layer a step (`counts/ssd.py`) over the peak bandwidth; the share is that over
the device time of the kernel `ssd_decode`, found by its own name. Rows and
steps are the deployment's samples at each sync inside the traced interval.
None on a program without a state-space mixer, or whose update is not that
kernel."""

from perfbench import trace_reduce
from perfbench.counts import ssd
from perfbench.readers import ops_match


def read(run: dict, args: dict):
    trace, sizes = run.get("trace"), run["sizes"]
    if not trace or not run.get("peaks") or not sizes.get("ssm_heads"):
        return None
    _, seconds = trace_reduce.seconds_of(trace["ops"], "ssd_decode")
    syncs = ops_match.traced_syncs(run)
    if not seconds or not syncs:
        return None
    needed = sum(ssd.decode_bytes((s[3] or 1) * s[1], sizes) for s in syncs)
    return 100.0 * needed / run["peaks"]["hbm_bytes_per_s"] / seconds
