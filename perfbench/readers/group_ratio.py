"""`counter_ratio.py` for counters that only some programs have: the ratio of
two of the engine's counters (`LLMServer.stats()`), each as its growth over the
window, or None where the program has no such group (a tree from before the
mechanism: the metric is left out of the line, nothing raises). args: num, den
= dotted paths into stats(); scale (default 1; 100 for a share in %)."""

from perfbench.readers import counter_ratio


def read(run: dict, args: dict):
    stats = run["counters"]["close"]["stats"]
    for path in (args["num"], args["den"]):
        group = stats
        for key in path.split("."):
            if not isinstance(group, dict) or key not in group:
                return None
            group = group[key]
    return counter_ratio.read(run, args)
