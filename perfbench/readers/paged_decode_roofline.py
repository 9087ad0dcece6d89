"""`paged_decode`'s share (%) of its roofline in the traced window. The kernel
is HBM-bound: the least time is the bytes it must read (every cached K and V of
every active sequence, once a step, `flops.paged_decode_bytes`) over the chip's
peak bandwidth; the share is that over the kernel's device time. The context
lengths are the deployment's samples at each sync inside the traced window; a
chunk of n steps reads the cache n times."""

from perfbench import flops, trace_reduce


def read(run: dict, args: dict):
    trace = run.get("trace")
    if not trace or not run.get("peaks"):
        return None
    _, seconds = trace_reduce.seconds_of(trace["ops"], "paged_decode")
    syncs = [s for s in run["engine"]["syncs"]
             if trace["t0"] <= s[0] <= trace["t1"]]
    if not seconds or not syncs:
        return None
    needed = sum(flops.paged_decode_bytes((s[3] or 1) * s[5], run["sizes"])
                 for s in syncs)
    return 100.0 * needed / run["peaks"]["hbm_bytes_per_s"] / seconds
