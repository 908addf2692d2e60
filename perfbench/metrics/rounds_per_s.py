"""Federated rounds completed in the window over the window's wall time."""


def read(run):
    if run.kind != "federated" or run.window_s <= 0:
        return None
    return run.work["rounds"] / run.window_s
