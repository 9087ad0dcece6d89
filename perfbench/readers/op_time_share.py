"""Share (%) of device busy time spent in operations whose name contains
`needle` (a kernel's stable name), from the trace's operation line."""

from perfbench import trace_reduce


def read(run: dict, args: dict):
    trace = run.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    _, seconds = trace_reduce.seconds_of(trace["ops"], args["needle"])
    return 100.0 * seconds / trace["devices"] / trace["busy_s"]
