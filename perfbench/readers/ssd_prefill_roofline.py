"""The chunked SSD program's share (%) of its roofline in the prefill chunks of
the traced seconds: the least time of a layer's call over a chunk's real
tokens (`counts/ssd.py`: the larger of its operations over the peak bf16 rate
and its bytes over the peak bandwidth), times the layers, summed over the
prefill chunks the deployment recorded inside the traced interval, over the
device time of the program's operations (args: ops, needle groups as
`ops_time_share.py` takes them). A bucket's padding is computed and not
counted: it is not work the algorithm needs. None on a program without a
state-space mixer."""

from perfbench.counts import ssd
from perfbench.readers import ops_match


def read(run: dict, args: dict):
    trace, sizes = run.get("trace"), run["sizes"]
    if not trace or not run.get("peaks") or not sizes.get("ssm_heads"):
        return None
    chunks = [c for c in run["engine"]["prefill_chunks"]
              if trace["t0"] <= c[0] <= trace["t1"]]
    _, seconds = ops_match.seconds_of(trace["ops"], args["ops"])
    if not seconds or not chunks:
        return None
    least = sum(ssd.chunked_least_seconds(c[1], sizes, run["peaks"])
                for c in chunks) * ssd.mixer_layers(sizes)
    return 100.0 * least / seconds
