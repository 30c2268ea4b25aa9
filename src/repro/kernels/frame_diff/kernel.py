"""Per-region frame differencing Pallas kernel (the Skip operator's signal).

Computes mean |frame_t − frame_{t−1}| over a (RY × RX) grid of regions —
the cheap "is anything happening here?" statistic the semantic optimizer's
Skip(N, condition) operator evaluates before invoking the MLLM.

Grid: (B,); each program reads one whole (C, H, W) frame pair and pools
every region at once (``kernels/pixel.py``), so the block shapes equal the
frame's own dimensions whatever the region grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.pixel import diff_grid


def _diff_kernel(a_ref, b_ref, o_ref, *, regions):
    o_ref[0] = diff_grid(a_ref[0], b_ref[0], regions)


def frame_diff_kernel(cur: jax.Array, prev: jax.Array, *, regions=(4, 4),
                      interpret: bool = False) -> jax.Array:
    """cur/prev (B, C, H, W) uint8 -> (B, RY, RX) f32 mean abs diff in [0,1]."""
    b, c, h, w = cur.shape
    ry, rx = regions
    assert h % ry == 0 and w % rx == 0
    frame = pl.BlockSpec((1, c, h, w), lambda i: (i, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_diff_kernel, regions=regions),
        grid=(b,),
        in_specs=[frame, frame],
        out_specs=pl.BlockSpec((1, ry, rx), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, ry, rx), jnp.float32),
        interpret=interpret,
    )(cur, prev)
