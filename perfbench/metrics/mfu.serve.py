"""2 x matmul parameters per generated token, times the traced window's
generated tokens per second, over the chip's peak bf16 rate."""
from bench import flops
from metrics import _device


def read(run):
    if run.kind != "serving" or run.trace is None or not run.traced:
        return None
    tokens = run.traced.get("tokens", 0.0)
    if tokens <= 0:
        return None
    rate = flops.serve_token_flops(run.dims) * tokens / run.trace.window_s
    return 100.0 * rate / (run.peaks["bf16_flops_per_s"] * run.device["count"])
