"""Model FLOPs of a round (forward, and the backward adapter training
needs, no recompute; `bench/flops.py`) times the traced window's rounds
per second, over the chip's peak bf16 rate."""
from bench import flops
from metrics import _device


def read(run):
    n = _device.units(run)
    if run.kind != "federated" or run.trace is None or n is None:
        return None
    per_round = flops.train_round_flops(run.dims, run.cell.traffic["cohort"])
    rate = per_round * n / run.trace.window_s
    return 100.0 * rate / (run.peaks["bf16_flops_per_s"] * run.device["count"])
