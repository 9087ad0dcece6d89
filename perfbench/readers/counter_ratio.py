"""Ratio of two of the engine's exact counters (`LLMServer.stats()`), each as
its growth over the window. args: num, den = dotted paths into stats();
scale (default 1; 100 for a share in %)."""


def _at(stats: dict, path: str):
    for key in path.split("."):
        stats = stats[key]
    return stats


def read(run: dict, args: dict):
    before, after = (run["counters"][k]["stats"] for k in ("open", "close"))
    den = _at(after, args["den"]) - _at(before, args["den"])
    if den <= 0:
        return None
    num = _at(after, args["num"]) - _at(before, args["num"])
    return args.get("scale", 1) * num / den
