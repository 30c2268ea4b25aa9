"""Fused Crop+Downscale+Normalize(+Greyscale) Pallas kernel.

This is the TPU-native realization of the Saṃsāra semantic-optimization
data-reduction operators: instead of separate Crop → Downscale → Normalize
passes (3× HBM round trips on the raw frame), a single kernel reads each raw
uint8 frame once and emits the reduced f32 frame.

Layout: frames are channels-first (B, C, H, W) uint8 (W lanes).  The crop
is a static slice of the loaded frame, so any in-frame region works.
Downscale is area-averaging by an integer factor f (``kernels/pixel.py``).

Grid: (B,).  VMEM per program: the raw (C, H, W) frame plus its f32
planes — about 0.5 MiB for the 3×128×256 streaming shape.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pixel


def _preproc_kernel(x_ref, o_ref, *, crop, factor: int,
                    mean: Tuple[float, ...], std: Tuple[float, ...],
                    grey: bool):
    out = pixel.preprocess(pixel.planes(x_ref[0]), crop, factor, mean, std,
                           grey)
    for k, p in enumerate(out):
        o_ref[0, k] = p.astype(o_ref.dtype)


def fused_preprocess_kernel(
    frames: jax.Array, *, crop: Tuple[int, int, int, int], factor: int = 1,
    mean: Tuple[float, ...] = (0.5, 0.5, 0.5),
    std: Tuple[float, ...] = (0.25, 0.25, 0.25), grey: bool = False,
    out_dtype=jnp.float32, interpret: bool = False,
) -> jax.Array:
    """frames (B, C, H, W) uint8; crop (y0, x0, h, w) -> (B, C', h/f, w/f)."""
    b, c, h, w = frames.shape
    y0, x0, ch, cw = crop
    assert y0 + ch <= h and x0 + cw <= w, "crop outside frame"
    assert ch % factor == 0 and cw % factor == 0, "crop not divisible by f"
    c_out = 1 if grey else c
    h_out, w_out = ch // factor, cw // factor

    return pl.pallas_call(
        functools.partial(_preproc_kernel, crop=crop, factor=factor,
                          mean=mean, std=std, grey=grey),
        grid=(b,),
        in_specs=[pl.BlockSpec((1, c, h, w), lambda i: (i, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, c_out, h_out, w_out),
                               lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, c_out, h_out, w_out), out_dtype),
        interpret=interpret,
    )(frames)
