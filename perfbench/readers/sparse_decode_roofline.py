"""The sparse decode attention's share (%) of its roofline in the traced
window. It is HBM-bound: the least time is the bytes it must read (the
indexer's key of every context token and K and V of the selected ones, once a
step and a layer, `counts/sparse_attention.py`) over the chip's peak bandwidth;
the share is that over the device time of its operations (args: ops, needle
groups as `ops_time_share.py` takes them: the indexer's page gather and scores,
the top-k, the row gather, the attention over the selected rows). The contexts
are the deployment's samples at each sync inside the traced window (a chunk of
n steps reads n times); the selected share of them is the window's own
(`stats()["sparse"]`). None on a program without the mechanism."""

from perfbench.counts import sparse_attention
from perfbench.readers import ops_match


def read(run: dict, args: dict):
    trace = run.get("trace")
    if not trace or not run.get("peaks"):
        return None
    before, after = (run["counters"][k]["stats"].get("sparse")
                     for k in ("open", "close"))
    if not before or not after:
        return None
    context = after["context_keys"] - before["context_keys"]
    if context <= 0:
        return None
    kept = (after["selected_keys"] - before["selected_keys"]) / context
    _, seconds = ops_match.seconds_of(trace["ops"], args["ops"])
    syncs = ops_match.traced_syncs(run)
    if not seconds or not syncs:
        return None
    needed = sum(sparse_attention.sparse_decode_bytes(
        (s[3] or 1) * s[5], kept * (s[3] or 1) * s[5], run["sizes"])
        for s in syncs)
    return 100.0 * needed / run["peaks"]["hbm_bytes_per_s"] / seconds
