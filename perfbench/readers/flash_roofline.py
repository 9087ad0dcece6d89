"""The flash attention kernels' share (%) of their roofline over the traced
train steps: compute-bound, so the least time is the operations causal
attention needs forward and backward (`flops.flash_train_flops`) over the
chips' peak; the share is that over the device time of `flash_fwd`,
`flash_bwd_dq` and `flash_bwd_dkv` summed over the chips."""

from perfbench import flops, trace_reduce


def read(run: dict, args: dict):
    trace = run.get("trace")
    if not trace or not run.get("peaks"):
        return None
    _, seconds = trace_reduce.seconds_of(trace["ops"], "flash_fwd", "flash_bwd")
    if not seconds:
        return None
    train = run["train"]
    needed = trace["steps"] * flops.flash_train_flops(
        run["sizes"], train["rows"], train["seq_len"])
    return 100.0 * needed / run["peaks"]["bf16_flops"] / seconds
