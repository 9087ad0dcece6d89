"""Exposed collective time as a share (%) of the traced window: seconds of
all-gather, reduce-scatter, all-reduce and the like on a device's operation
line, which is serial with compute, averaged over the chips. What the compiler
hid under compute is not on that line and is not counted."""


def read(run: dict, args: dict):
    trace = run.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * trace["collective_s"] / trace["window_s"]
