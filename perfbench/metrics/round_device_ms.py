"""Device busy time per round in the traced window."""
from metrics import _device


def read(run):
    n = _device.units(run)
    if run.kind != "federated" or run.trace is None or n is None:
        return None
    return 1e3 * run.trace.busy_s / n
