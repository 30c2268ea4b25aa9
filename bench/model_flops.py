"""Operations and bytes a served frame needs, from shapes alone.

Counted as multiply-adds × 2 for every matmul and convolution the
extract's mathematics needs; attention counts the causal half of the
score and value products.  Elementwise work is left out.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

STEM_CH = 48
N_TASK_TOKENS = 12          # 6 scalar tasks + 6 plate characters
HEAD_WIDTH = 2 + 6 + 6 + 4 + 7 + 2 + 6 * 36


def tokens(patch: int, frame_shape: Sequence[int]) -> int:
    _, h, w = frame_shape
    p = patch // 4
    return (h // 4 // p) * (w // 4 // p) + N_TASK_TOKENS


def layer_flops(arch: Dict[str, Any], s: int) -> int:
    d, f = arch["d_model"], arch["d_ff"]
    a = arch["attention"]
    hq, hkv, hd = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    proj = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    mlp = (3 if arch.get("mlp_gated", True) else 2) * d * f
    attn = 2 * hq * hd * s * (s + 1) // 2          # QK^T and PV, causal
    return 2 * s * (proj + mlp) + 2 * attn


def extract_flops(arch: Dict[str, Any], patch: int,
                  frame_shape: Sequence[int]) -> int:
    c, h, w = frame_shape
    s = tokens(patch, frame_shape)
    p = patch // 4
    h2, w2 = h // 2, w // 2
    h4, w4 = h // 4, w // 4
    stem = 2 * (h2 * w2 * 9 * c * STEM_CH + h4 * w4 * 9 * STEM_CH * STEM_CH)
    proj = 2 * (s - N_TASK_TOKENS) * STEM_CH * p * p * arch["d_model"]
    heads = 2 * arch["d_model"] * HEAD_WIDTH
    return stem + proj + arch["n_layers"] * layer_flops(arch, s) + heads


def frame_diff_bytes(n: int, frame_shape: Sequence[int],
                     regions: Sequence[int]) -> int:
    """Read the frame and its predecessor, write the region grid (f32)."""
    c, h, w = frame_shape
    return n * (2 * c * h * w + 4 * regions[0] * regions[1])


def preprocess_bytes(n: int, channels: int, crop: Sequence[int],
                     factor: int) -> int:
    """Read the crop (uint8), write the pooled frame (f32)."""
    _, _, ch, cw = crop
    return n * channels * (ch * cw + 4 * (ch // factor) * (cw // factor))
