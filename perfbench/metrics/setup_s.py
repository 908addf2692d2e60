"""Set-up time: process start to the first timed unit of work (loading,
weights from the seed, compiles or cache loads, warm-up)."""


def read(run):
    return run.setup_s
