"""Int8×int8→int32 matmul with per-channel scales (physical-opt quantization).

Grid (M/bm, N/bn, K/bk), K innermost: int32 accumulation lives in VMEM
scratch across K steps; scales applied once at the final step.  MXU-friendly
tile defaults (bm=bn=256, bk=512 int8 => 128KiB per operand panel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _int8_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_ref, *, nk: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = w_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)

    @pl.when(ik == nk - 1)
    def _finish():
        sx = sx_ref[...]                              # (bm, 1) f32
        sw = sw_ref[...]                              # (1, bn) f32
        o_ref[...] = (acc_ref[...].astype(jnp.float32) * sx * sw).astype(
            o_ref.dtype)


def int8_matmul_kernel(x: jax.Array, w: jax.Array, sx: jax.Array,
                       sw: jax.Array, *, bm: int = 256, bn: int = 256,
                       bk: int = 512, out_dtype=jnp.float32,
                       interpret: bool = False) -> jax.Array:
    """x (M,K) int8, w (K,N) int8, sx (M,1) f32, sw (1,N) f32 -> (M,N)."""
    m, k = x.shape
    n = w.shape[1]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    nk = k // bk

    return pl.pallas_call(
        functools.partial(_int8_kernel, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x, w, sx, sw)


