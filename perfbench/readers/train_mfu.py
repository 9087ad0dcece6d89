"""Model FLOP/s utilization (%) of the traced train steps: the operations a
step's tokens need forward and backward (`flops.train_flops_per_token`,
recomputation not counted) over the device time of the step program in the
trace (per execution, per chip) times the chips' peak of
`perfbench/peaks.json`. Device time and not the traced run's wall clock: that
holds the profiler's start and stop and moves with the window's length."""

from perfbench import flops, trace_reduce


def read(run: dict, args: dict):
    trace = run.get("trace")
    if not trace or not run.get("peaks"):
        return None
    count, seconds = trace_reduce.seconds_of(trace["modules"], args["needle"])
    if not count or not seconds:
        return None
    train = run["train"]
    needed = train["tokens_per_step"] * flops.train_flops_per_token(
        run["sizes"], train["seq_len"])
    return 100.0 * needed / (run["chips"] * run["peaks"]["bf16_flops"]
                             * seconds / count)
