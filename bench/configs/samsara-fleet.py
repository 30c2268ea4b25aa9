"""The backbone of ``samsara-fleet``: the dense GQA decoder.  Its entry
as the program takes it and its weights' fan-in come from ``fleet``, its
plain reference from ``reference``, its FLOPs from ``model_flops``."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fleet import arch_config, fan_in  # noqa: E402,F401
from model_flops import extract_flops  # noqa: E402
from reference import Reference  # noqa: E402


def flops_per_frame(config, variant, frame_shape):
    """FLOPs one frame of shape ``(C, h, w)`` needs in the extract."""
    return extract_flops(config["backbone"][variant], config["patch"],
                         frame_shape)


def reference(spec, patch):
    """The plain float32 reference of one variant."""
    return Reference(spec, patch)
