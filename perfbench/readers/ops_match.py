"""(count, seconds) of the operations of a reduced trace's table that belong
to one mechanism. `groups` is a list of needle lists; a name matches when it
holds every needle of some group. `trace_reduce.seconds_of` is the case of
one-needle groups."""


def seconds_of(table: dict, groups) -> tuple:
    count, seconds = 0, 0.0
    for name, (c, s) in table.items():
        if any(all(n in name for n in group) for group in groups):
            count, seconds = count + c, seconds + s
    return count, seconds


def traced_syncs(run: dict) -> list:
    """The engine's decode syncs inside the traced interval."""
    trace = run["trace"]
    return [s for s in run["engine"]["syncs"]
            if trace["t0"] <= s[0] <= trace["t1"]]
