"""Share (%) of the engine loop's wall time in the two phases that copy a
slot's recurrent state (`decode.phase_s.state_save` into a snapshot,
`decode.phase_s.state_restore` out of one) over `decode.loop_s`, each as its
growth over the window. None on a program that has no such phases (a model
without linear-attention layers, or a tree from before them)."""


def read(run: dict, args: dict):
    before, after = (run["counters"][k]["stats"]["decode"]
                     for k in ("open", "close"))
    phases = after.get("phase_s", {})
    if "state_save" not in phases or "state_restore" not in phases:
        return None
    loop = after["loop_s"] - before["loop_s"]
    if loop <= 0:
        return None
    spent = sum(after["phase_s"][k] - before["phase_s"][k]
                for k in ("state_save", "state_restore"))
    return 100.0 * spent / loop
