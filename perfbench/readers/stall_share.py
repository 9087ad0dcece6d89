"""Share of the engine loop's wall time spent in STALLS: watched reads
(`decode_sync`, the first token's) that outlasted their work by the engine's
own test (`ray_tpu/util/tracing.py`: at least STALL_FLOOR_S and more than
STALL_FACTOR times the mean of the phase's recent entries). The growth over the
window of `stats()["decode"]["stall_s"]`, summed over its keys, over the
growth of `decode.loop_s`, in %. 0.0 for a window without a stall.

A program from before the engine counted its stalls (no `decode.stall_s`) has
nothing to read: None, and the metric is left out of the line."""


def read(run: dict, args: dict):
    before, after = (run["counters"][k]["stats"]["decode"]
                     for k in ("open", "close"))
    if "stall_s" not in after:
        return None
    loop_s = after["loop_s"] - before["loop_s"]
    if loop_s <= 0:
        return None
    stalled = sum(after["stall_s"].values()) - sum(before["stall_s"].values())
    return 100.0 * stalled / loop_s
