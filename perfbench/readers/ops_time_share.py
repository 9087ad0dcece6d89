"""Share (%) of device busy time spent in the operations of one mechanism,
from the trace's operation line. args: ops = a list of needle groups; an
operation counts when its name holds EVERY needle of SOME group (`op_time_
share.py` takes one needle: a mechanism made of XLA operations has several
names, each with its result's type and dimensions). None where none ran."""

from perfbench.readers import ops_match


def read(run: dict, args: dict):
    trace = run.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    count, seconds = ops_match.seconds_of(trace["ops"], args["ops"])
    if not count:
        return None
    return 100.0 * seconds / trace["devices"] / trace["busy_s"]
