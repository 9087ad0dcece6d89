"""Ratio of two of the engine loop's own counters (`LLMServer.stats()`:
`decode.phase_s.<phase>`, `decode.loop_s`, the work counters beside them),
each as its growth over the window: `counter_ratio.py` with its args (num,
den = dotted paths into stats(); scale), on a program that has the counters.

A program from before the loop counted its phases (no `decode.loop_s`) has
nothing to read: None, and the metric is left out of the line. On a program
that has them a path that does not resolve raises, as a misspelt metric file
should."""

from perfbench.readers import counter_ratio


def read(run: dict, args: dict):
    if "loop_s" not in run["counters"]["close"]["stats"]["decode"]:
        return None
    return counter_ratio.read(run, args)
