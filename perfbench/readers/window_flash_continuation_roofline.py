"""The continuation kernels' share (%) of the bf16 peak in the traced seconds
(`flash_continuation` of the full layers, `flash_continuation_window` of the
sliding ones): the operations of the (query, key) pairs the chunks' real
queries see (`counts/window_attention.py`, from the pairs the engine counted
at each continuation chunk's dispatch inside the traced seconds) over the
peak, over the device time of both kernels' events. None on a program without
such layers, and where no continuation chunk ran in the traced seconds."""

from perfbench import trace_reduce
from perfbench.counts import window_attention
from perfbench.readers.window_paged_decode_roofline import traced


def read(run: dict, args: dict):
    chunks = traced(run, "recent_continuations")
    if not chunks or not run.get("peaks"):
        return None
    _, seconds = trace_reduce.seconds_of(run["trace"]["ops"],
                                         "flash_continuation")
    if not seconds:
        return None
    flops = window_attention.continuation_flops(
        sum(r[1] for r in chunks), sum(r[2] for r in chunks), run["sizes"])
    return 100.0 * flops / run["peaks"]["bf16_flops"] / seconds
