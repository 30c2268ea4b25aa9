"""Backbone: model FLOPs of the real frames the extract served, over the
traced window × the chip's bf16 peak, %.  Padding rows do not count."""


def read(run):
    tr = run["trace"]
    if tr["window_s"] <= 0:
        return None
    mod = run["cell"]["model"]
    cfg = run["config"]
    flops = 0
    for _, req in run["requests"]:
        flops += req.n * mod.flops_per_frame(cfg, req.variant,
                                             req.frames.shape[1:])
    if not flops:
        return None
    return 100.0 * flops / (tr["window_s"] * run["peak"]["bf16_flops_per_s"])
