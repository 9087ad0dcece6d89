"""A number the chip-bound actor recorded during set-up. args: key = dotted
path into the run's set-up facts (`compile.compile_s`: jax's compile events up
to the window; `actor_ready_s`: spawn of the actor to its first answer, i.e.
worker start, chip binding, backend up and weights placed)."""


def read(run: dict, args: dict):
    value = run["setup"]
    for key in args["key"].split("."):
        value = value[key]
    return value
