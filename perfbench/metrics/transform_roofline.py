"""Least time of the upload transform over its measured kernel time.  The
least time is the transform's own bytes at the chip's HBM bandwidth: every
client's float32 delta read once and its coded message written once
(`bench/flops.py` `transform_bytes`), whatever passes the kernels make."""
from bench import flops, spec
from metrics import _device
from references import dense_lm

_kernels = spec.metric_module("transform_kernel_ms")


def read(run):
    n = _device.units(run)
    if run.kind != "federated" or run.trace is None or n is None:
        return None
    s, count = _kernels.kernel_seconds(run)
    if count == 0 or s <= 0:
        return None
    tr = run.cell.traffic
    st = tr["strategy"]
    p_len = dense_lm.lora_size(run.dims)
    least = flops.transform_bytes(p_len, tr["cohort"]["clients"],
                                  st.get("density_up", 1.0),
                                  int(st.get("quant_bits_up", 0)))
    least_s = least / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (s / n)
