"""Mean number of active slots over the window's decode syncs, sampled by the
deployment each time the engine records a sync (`_note_sync`)."""


def read(run: dict, args: dict):
    syncs = run["engine"]["syncs"]
    return sum(s[1] for s in syncs) / len(syncs) if syncs else None
