"""Pixel kernels: ``frame_diff``'s share of its roofline, %.

Least time = the bytes the diff needs (each frame and its predecessor
read, the region grid written; ``model_flops.frame_diff_bytes``) over
the chip's HBM bandwidth, for every call in the trace (its batch size
read from the kernel's output shape).  Device time = the ``frame_diff``
kernel's events in the trace.
"""
import model_flops
from kernel_shapes import leading_dim

KERNEL = "frame_diff"


def read(run):
    calls = run["trace"]["kernels"].get(KERNEL, {})
    t = sum(s for _, s in calls.values())
    if t <= 0:
        return None
    cfg = run["config"]
    skip = [op for ops in cfg["prefix"].values() for op in ops
            if op["op"] == "skip"][0]
    nbytes = sum(n * model_flops.frame_diff_bytes(
        leading_dim(shape), cfg["frame"], skip["regions"])
        for shape, (n, _) in calls.items())
    return 100.0 * nbytes / run["peak"]["hbm_bytes_per_s"] / t
