"""Fused surviving-frame prefix Pallas kernel.

Extends ``fused_preprocess`` with the rest of the streaming prefix: one
program per frame reads the raw (C, H, W) uint8 frame (plus its
predecessor when a diff stage is present) from HBM **once** and emits
every per-frame statistic the chain needs — the (RY, RX) frame-diff
activity grid, one near-color pixel fraction per cheap filter, the
cropped/downscaled/normalized frame, and the semantic-gate signature
pooling — as separate outputs of a single ``pl.pallas_call``.  The
embedding projection (a tiny (B, D) @ (D, 16) matmul) runs outside the
kernel on the same device, inside the same jit.

Grid: (B,).  VMEM per program: the raw frame pair as f32 plus the
reduced intermediates — ≤ ~1 MiB for the 3×128×256 streaming shape, in
budget.

The diff and preprocess stages call the in-kernel helpers of the
``frame_diff`` and ``fused_preprocess`` kernels (``kernels/pixel.py``), so
on the chip the fused pass and the unfused ops compute those stages the
same way; the other stages follow ``ref.fused_prefix_ref`` stage by
stage.  The sweep test ``tests/test_kernels.py::test_fused_prefix_sweep``
pins interpret-mode output to the oracle.
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import pixel
from repro.kernels.fused_prefix.ref import project_rowwise

#: the normalization constants of ``FusedPreprocessOp`` (its defaults)
_MEAN = (0.5, 0.5, 0.5)
_STD = (0.25, 0.25, 0.25)


def out_frame_shape(spec, shape: Tuple[int, int, int]
                    ) -> Tuple[int, int, int]:
    """(C, H, W) after the spec's transform stages."""
    c, h, w = shape
    for stage in spec:
        if stage[0] == "crop":
            h, w = stage[1][2], stage[1][3]
        elif stage[0] == "preprocess":
            _, crop, factor, grey = stage
            h, w = crop[2] // factor, crop[3] // factor
            # grey output is host-re-expanded to 3 channels; c unchanged
    return c, h, w


def _color_frac(ps, rgb, roi) -> jax.Array:
    """Fraction of the ROI's pixels within distance 70 of ``rgb``
    (``CheapColorFilterOp``'s statistic; normalized frames are mapped
    back to the pixel range first, decided on the ROI's own maximum)."""
    if roi is not None:
        y0, x0, h, w = roi
        ps = [p[y0:y0 + h, x0:x0 + w] for p in ps]
    norm = pixel.frame_max(ps) <= 8.0
    sq = None
    for k, p in enumerate(ps):
        p = jnp.where(norm, (p * 0.25 + 0.5) * 255.0, p)
        d = (p - float(rgb[k])) ** 2
        sq = d if sq is None else sq + d
    near = (jnp.sqrt(sq) < 70.0).astype(jnp.float32)
    return near.sum() / float(near.size)


def _signature_grid(ps, gy: int, gx: int) -> List[jax.Array]:
    """Per-channel (gy, gx) patch means of the gate signature, after the
    per-frame raw-pixel normalization of ``TemporalSignature``."""
    raw = pixel.frame_max(ps) > 8.0
    h, w = ps[0].shape
    out = []
    for p in ps:
        p = jnp.where(raw, (p / 255.0 - 0.5) / 0.25, p)
        out.append(pixel.pool_sum(p, gy, gx) / float((h // gy) * (w // gx)))
    return out


def _prefix_kernel(*refs, spec):
    """One frame: walk the stages, writing each statistic's output ref."""
    it = iter(refs)
    has_diff = any(s[0] == "diff" for s in spec)
    ncolor = sum(1 for s in spec if s[0] == "color")
    x_ref = next(it)
    prev_ref = next(it) if has_diff else None
    d_ref = next(it) if has_diff else None
    frac_ref = next(it) if ncolor else None
    o_ref = next(it)
    feat_ref = next(it) if any(s[0] == "signature" for s in spec) else None

    ps = pixel.planes(x_ref[0])
    fracs = []
    for stage in spec:
        kind = stage[0]
        if kind == "diff":
            d_ref[0] = pixel.diff_grid(x_ref[0], prev_ref[0], stage[1])
        elif kind == "color":
            fracs.append(_color_frac(ps, stage[1], stage[2]))
        elif kind == "crop":
            y0, x0, h, w = stage[1]
            ps = [p[y0:y0 + h, x0:x0 + w] for p in ps]
        elif kind == "preprocess":
            _, crop, factor, grey = stage
            ps = pixel.preprocess(ps, crop, factor, _MEAN, _STD, grey)
            if grey:
                ps = ps * len(_MEAN)                  # host-repeat inlined
        elif kind == "signature":
            for k, g in enumerate(_signature_grid(ps, *stage[1])):
                feat_ref[0, k] = g
    if ncolor:
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, ncolor), 1)
        row = jnp.zeros((1, ncolor), jnp.float32)
        for j, f in enumerate(fracs):
            row = jnp.where(lane == j, f, row)
        frac_ref[0] = row
    for k, p in enumerate(ps):
        if jnp.issubdtype(o_ref.dtype, jnp.integer):
            p = p.astype(jnp.int32)             # Mosaic: no f32 -> uint8
        o_ref[0, k] = p.astype(o_ref.dtype)


def fused_prefix_kernel(frames: jax.Array, prevs=None, proj=None, *,
                        spec, interpret: bool = False):
    """frames (B, C, H, W); returns (d, fracs, x, feats, emb) like the
    oracle (absent stages -> None / empty tuple)."""
    b, c, h, w = frames.shape
    has_diff = any(s[0] == "diff" for s in spec)
    has_sig = any(s[0] == "signature" for s in spec)
    ncolor = sum(1 for s in spec if s[0] == "color")
    oc, oh, ow = out_frame_shape(spec, (c, h, w))

    # every block spans its array's trailing dims: Mosaic takes any such
    # block, where a (1, n) row block of a (B, n) array breaks its
    # (8, 128) tiling rule — hence the (B, 1, n) / (B, C, gy, gx)
    # layouts, reshaped back outside the kernel
    def block(*dims):
        return pl.BlockSpec((1,) + dims, lambda i: (i,) + (0,) * len(dims))

    in_specs = [block(c, h, w)] * (2 if has_diff else 1)
    out_specs, out_shape = [], []

    def out(dims, dtype):
        out_specs.append(block(*dims))
        out_shape.append(jax.ShapeDtypeStruct((b,) + dims, dtype))

    if has_diff:
        out(next(s[1] for s in spec if s[0] == "diff"), jnp.float32)
    if ncolor:
        out((1, ncolor), jnp.float32)
    out((oc, oh, ow), jnp.float32 if any(s[0] == "preprocess" for s in spec)
        else frames.dtype)
    if has_sig:
        out((oc,) + next(s[1] for s in spec if s[0] == "signature"),
            jnp.float32)

    args = (frames, prevs) if has_diff else (frames,)
    outs = list(pl.pallas_call(
        functools.partial(_prefix_kernel, spec=spec),
        grid=(b,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args))

    d = outs.pop(0) if has_diff else None
    fracs = tuple(outs.pop(0)[:, 0].T) if ncolor else ()
    x = outs.pop(0)
    feats = emb = None
    if has_sig:
        feats = outs.pop(0).reshape(b, -1)
        emb = project_rowwise(feats, proj)
    return d, fracs, x, feats, emb
