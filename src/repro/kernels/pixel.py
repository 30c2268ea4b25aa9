"""In-kernel pixel helpers shared by the stream-path Pallas kernels.

The TPU compiler (Mosaic) has no uint8 -> float32 cast and cannot split
the lane (last) dimension of a value, so pixels widen through int32 and
region pooling is two matmuls with 0/1 window matrices instead of a
reshape-and-mean.  On integer pixel values the pooled sums are exact
(HIGHEST precision, every partial sum below 2**24), so ``frame_diff`` and
``fused_prefix`` compute bitwise the same diff grid, and ``fused_preprocess``
and ``fused_prefix`` the same downscaled frame, at any batch size.

Every frame is handled as one (H, W) float32 plane per channel; the
kernels built on these helpers run one program per frame.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST

#: luminance weights of the grey conversion (ITU-R BT.601)
LUM = (0.299, 0.587, 0.114)


def planes(x) -> List[jax.Array]:
    """(C, H, W) frame of any dtype -> C float32 (H, W) planes.  Integer
    pixels widen through int32: Mosaic refuses uint8 -> float32."""
    if jnp.issubdtype(x.dtype, jnp.integer):
        x = x.astype(jnp.int32)
    x = x.astype(jnp.float32)
    return [x[k] for k in range(x.shape[0])]


def _windows(n: int, parts: int, axis: int) -> jax.Array:
    """0/1 float32 matrix that sums ``n`` positions into ``parts`` equal
    consecutive windows: (parts, n) for axis 0, (n, parts) for axis 1."""
    shape = (parts, n) if axis == 0 else (n, parts)
    pos = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - axis)
    lo = jax.lax.broadcasted_iota(jnp.int32, shape, axis) * (n // parts)
    return ((pos >= lo) & (pos < lo + n // parts)).astype(jnp.float32)


def pool_sum(x: jax.Array, ry: int, rx: int) -> jax.Array:
    """(H, W) -> (ry, rx): the sum of each (H/ry × W/rx) cell."""
    h, w = x.shape
    rows = jnp.dot(_windows(h, ry, 0), x, precision=_HIGHEST,
                   preferred_element_type=jnp.float32)
    return jnp.dot(rows, _windows(w, rx, 1), precision=_HIGHEST,
                   preferred_element_type=jnp.float32)


def frame_max(ps: List[jax.Array]) -> jax.Array:
    """Scalar maximum over every plane (raw-vs-normalized test)."""
    m = ps[0].max()
    for p in ps[1:]:
        m = jnp.maximum(m, p.max())
    return m


def diff_grid(cur, prev, regions) -> jax.Array:
    """(C, H, W) frame pair -> (ry, rx) mean |cur - prev| / 255 per
    region; the per-region sum is exact on integer pixels."""
    a, b = planes(cur), planes(prev)
    _, h, w = cur.shape
    ry, rx = regions
    total = None
    for pa, pb in zip(a, b):
        s = pool_sum(jnp.abs(pa - pb), ry, rx)
        total = s if total is None else total + s
    return total / float(255 * len(a) * (h // ry) * (w // rx))


def preprocess(ps: List[jax.Array], crop, factor: int, mean, std,
               grey: bool) -> List[jax.Array]:
    """Raw-pixel planes -> cropped, area-downscaled, normalized planes
    (one plane when ``grey``)."""
    y0, x0, ch, cw = crop
    out = []
    for k, p in enumerate(ps):
        p = p[y0:y0 + ch, x0:x0 + cw]
        if factor > 1:
            p = pool_sum(p, ch // factor, cw // factor)
        p = p / float(255 * factor * factor)
        out.append((p - mean[k]) / std[k])
    if grey:
        g = out[0] * LUM[0]
        for k in range(1, len(out)):
            g = g + out[k] * LUM[k]
        out = [g]
    return out
