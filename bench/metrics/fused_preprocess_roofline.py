"""Pixel kernels: ``fused_preprocess``'s share of its roofline, %.

Least time = the bytes the op needs (the crop read as uint8, the pooled
frame written as f32; ``model_flops.preprocess_bytes``) over the chip's
HBM bandwidth, for every call in the trace: its batch size and crop
read from the kernel's output shape (``f32[n, C, h, w]`` is a crop of
``h*factor`` by ``w*factor``).  Device time = the ``fused_preprocess``
kernel's events in the trace.
"""
import model_flops
from kernel_shapes import dims

KERNEL = "fused_preprocess"


def read(run):
    calls = run["trace"]["kernels"].get(KERNEL, {})
    t = sum(s for _, s in calls.values())
    if t <= 0:
        return None
    pre = {}
    for ops in run["config"]["prefix"].values():
        for op in ops:
            if op["op"] == "fused_preprocess":
                f = op["factor"]
                pre[(op["crop"][2] // f, op["crop"][3] // f)] = op
    nbytes = 0
    for shape, (n, _) in calls.items():
        b, c, h, w = dims(shape)
        op = pre[(h, w)]
        nbytes += n * model_flops.preprocess_bytes(b, c, op["crop"],
                                                   op["factor"])
    return 100.0 * nbytes / run["peak"]["hbm_bytes_per_s"] / t
