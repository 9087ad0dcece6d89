"""`paged_decode_roofline.py` for a model whose keys and values are in its
full-attention layers only: `paged_decode`'s share (%) of its roofline in the
traced window, the bytes from `counts/hybrid_cache.py` (one layer in
`full_attn_every` is read, where `flops.kv_bytes_per_token` counts them all).
None on a program without linear-attention layers."""

from perfbench import trace_reduce
from perfbench.counts import hybrid_cache
from perfbench.readers import ops_match


def read(run: dict, args: dict):
    trace, sizes = run.get("trace"), run["sizes"]
    if not trace or not run.get("peaks") or not sizes.get("full_attn_every"):
        return None
    _, seconds = trace_reduce.seconds_of(trace["ops"], "paged_decode")
    syncs = ops_match.traced_syncs(run)
    if not seconds or not syncs:
        return None
    needed = sum(hybrid_cache.paged_decode_bytes((s[3] or 1) * s[5], sizes)
                 for s in syncs)
    return 100.0 * needed / run["peaks"]["hbm_bytes_per_s"] / seconds
