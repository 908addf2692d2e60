"""The device a run is on: the check that refuses anything but the chips a
cell asks for, the table of published peaks, and peak memory."""
from __future__ import annotations

import json
import os
from typing import Dict

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class NoAccelerator(SystemExit):
    """Raised before any work when the chips the cell needs are missing."""


def check(chips: int) -> Dict:
    """{'platform', 'kind', 'count'} of the devices this run uses; exits
    non-zero unless there are at least `chips` TPU devices."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: the first device is a "
                            f"{devs[0].platform!r} device; this cell needs "
                            f"{chips} TPU chip(s)")
    if len(devs) < chips:
        raise NoAccelerator(f"{len(devs)} TPU chip(s) found; this cell "
                            f"needs {chips}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def peaks(kind: str) -> Dict[str, float]:
    """Published peaks of one chip of `kind`; an unknown kind is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(table['devices'])}")
    return table["devices"][kind]


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first `chips` devices."""
    import jax
    out = 0
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        out = max(out, int(stats.get("peak_bytes_in_use", 0)))
    return out
