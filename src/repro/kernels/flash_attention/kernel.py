"""Flash attention (forward) Pallas TPU kernel.

Layout: q (B, Hk, G, S, D) — GQA q heads folded per kv head so one program
computes all G query heads that share a kv head.  k/v (B, Hk, S, D).

Grid: (B, Hk, nq, nk) with nk innermost — TPU executes the trailing grid
dimension sequentially, so the online-softmax state (m, l, acc) lives in VMEM
scratch across the nk steps of one (b, h, iq) cell.  Causal/local blocks that
cannot contribute are predicated off with ``pl.when`` (Mosaic skips the
compute; the BlockSpec copy of a skipped block is the only residual cost).

VMEM per program (defaults bq=bk=256, D=128, G≤8):
  q: G·bq·D·2B ≤ 512KiB   k,v: 2·bk·D·2B = 128KiB
  acc: G·bq·D·4B ≤ 1MiB   m,l: 2·G·bq·128·4B ≤ 1MiB      — well under 16MiB.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, cap: Optional[float], causal: bool,
                  window: Optional[int], bq: int, bk: int, nk: int):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * bq
    k_start = ik * bk

    run = True
    if causal:
        run = k_start <= q_start + bq - 1          # block intersects causal cone
    if window is not None:
        run = jnp.logical_and(run, k_start + bk - 1 > q_start - window)

    @pl.when(run)
    def _step():
        g, _, d = q_ref.shape[2], q_ref.shape[3], q_ref.shape[4]
        q = q_ref[0, 0].reshape(g * bq, d)          # (G·Bq, D)
        k = k_ref[0, 0]                              # (Bk, D)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q.astype(jnp.float32), k.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (G·Bq, Bk)
        if cap is not None:
            s = cap * jnp.tanh(s / cap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (g * bq, bk), 0) % bq
        # rows are G blocks of Bq query positions: row r -> position r % bq
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (g * bq, bk), 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = kpos <= qpos
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...][:, :1]                   # (rows, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                        # (rows, Bk)
        l_new = l_scr[...][:, :1] * alpha + jnp.sum(p, -1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # (rows, D)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _finish():
        g, d = q_ref.shape[2], q_ref.shape[4]
        l = l_scr[...][:, :1]
        out = acc_scr[...] / jnp.maximum(l, 1e-30)
        o_ref[0, 0] = out.reshape(g, bq, d).astype(o_ref.dtype)


def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True,
                           cap: Optional[float] = None,
                           window: Optional[int] = None,
                           bq: int = 256, bk: int = 256,
                           interpret: bool = False) -> jax.Array:
    """q (B, Hk, G, S, D); k, v (B, Hk, S, D) -> (B, Hk, G, S, D)."""
    b, hk, g, s, d = q.shape
    bq = min(bq, s)
    bk = min(bk, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    nq, nk = s // bq, s // bk
    scale = 1.0 / np.sqrt(d)

    grid = (b, hk, nq, nk)
    rows = g * bq

    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, cap=cap, causal=causal,
                          window=window, bq=bq, bk=bk, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, bq, d), lambda b_, h_, iq, ik: (b_, h_, 0, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, iq, ik: (b_, h_, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, iq, ik: (b_, h_, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, bq, d),
                               lambda b_, h_, iq, ik: (b_, h_, 0, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hk, g, s, d), q.dtype),
        scratch_shapes=_scratch(rows, d),
        interpret=interpret,
    )(q, k, v)


def _scratch(rows: int, d: int):
    """VMEM scratch for (m, l, acc) online-softmax state."""
    return [
        pltpu.VMEM((rows, LANES), jnp.float32),
        pltpu.VMEM((rows, LANES), jnp.float32),
        pltpu.VMEM((rows, d), jnp.float32),
    ]
