"""Device time per call of the engine's jitted decode program."""

PROGRAM = "jit__decode_impl"


def read(run):
    if run.kind != "serving" or run.trace is None:
        return None
    s, n = run.trace.module_seconds(lambda name: name == PROGRAM)
    return 1e3 * s / n if n else None
