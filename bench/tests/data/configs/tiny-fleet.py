"""Model FLOPs per served frame of ``tiny-fleet``."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))))

from model_flops import extract_flops  # noqa: E402


def flops_per_frame(config, variant, frame_shape):
    """FLOPs one frame of shape ``(C, h, w)`` needs in the extract."""
    return extract_flops(config["backbone"][variant], config["patch"],
                         frame_shape)
