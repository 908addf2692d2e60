"""Share of the traced window in which no operation ran on the device, in
the serving cells (1 - busy / window, from the device trace)."""
from metrics import _device


def read(run):
    return _device.idle_share(run, "serving")
