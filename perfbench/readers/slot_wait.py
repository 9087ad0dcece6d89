"""A percentile of the time requests waited in `LLMServer._reserve` for a free
slot and enough free pages, from the deployment's stamps round that call.
args: p."""

from perfbench import stats


def read(run: dict, args: dict):
    waits = [w[1] * 1e3 for w in run["engine"]["slot_waits"]]
    return stats.percentile(waits, args["p"]) if waits else None
