"""Device milliseconds of a jitted program from the trace's `XLA Modules`
line. args: needle = part of the program's name; per = "event" (per
execution) or "decode_step" (per decode step: the decode-chunk programs' time
over the steps the engine ran inside the traced window, a chunk of n being n
steps)."""

from perfbench import trace_reduce


def read(run: dict, args: dict):
    trace = run.get("trace")
    if not trace:
        return None
    count, seconds = trace_reduce.seconds_of(trace["modules"], args["needle"])
    if args["per"] == "decode_step":   # one device serves; a chunk of n is n steps
        count = sum(s[3] or 1 for s in run["engine"]["syncs"]
                    if trace["t0"] <= s[0] <= trace["t1"])
    if not count or not seconds:
        return None
    return seconds / count * 1e3
