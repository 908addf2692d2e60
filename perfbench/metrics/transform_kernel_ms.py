"""Device time per round of the upload transform's Pallas kernels, by
kernel function name: the magnitude pass, the bin counts, the fused
mask-quantize(-pack), the batched pack of the sparse aggregation, and the
threshold mask and count passes of the Top-K selector."""
from metrics import _device

KERNELS = ("_absmax_kernel", "_bin_kernel", "_fuse_kernel",
           "_fuse_pack_kernel", "_pack_batch_kernel", "_mask_kernel",
           "_count_kernel")


def kernel_seconds(run):
    return run.trace.op_seconds(
        lambda name: name.rsplit(":", 1)[-1] in KERNELS)


def read(run):
    n = _device.units(run)
    if run.kind != "federated" or run.trace is None or n is None:
        return None
    s, count = kernel_seconds(run)
    if count == 0:
        return None
    return 1e3 * s / n
