"""The backbone of ``tiny-ownref``: ``tiny-fleet``'s shapes, brought by a
module of its own.  Its reference delegates to the dense one; its fan-in
rule is its own, so its seeded weights differ from ``tiny-fleet``'s in
the leaves where the two rules differ, and nowhere else."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))))

import fleet  # noqa: E402
from fleet import arch_config  # noqa: E402,F401
from model_flops import extract_flops  # noqa: E402
from reference import Reference  # noqa: E402


def fan_in(name, shape):
    """The dense rule, but the gated MLP's down projection counts twice
    its rows: it sums the product of two unit-scale streams."""
    if name == "w_out":
        return 2 * shape[-2]
    return fleet.fan_in(name, shape)


class OwnReference:
    """The plain float32 reference of one variant: the dense one's logits,
    through an object of this module."""

    def __init__(self, spec, patch):
        self.dense = Reference(spec, patch)

    def logits(self, params, frames, block=16):
        return self.dense.logits(params, frames, block)


def reference(spec, patch):
    return OwnReference(spec, patch)


def flops_per_frame(config, variant, frame_shape):
    """FLOPs one frame of shape ``(C, h, w)`` needs in the extract."""
    return extract_flops(config["backbone"][variant], config["patch"],
                         frame_shape)
