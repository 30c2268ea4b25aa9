"""Public fused preprocessing op (the streaming pipeline's pixel hot path)."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.fused_preprocess.kernel import fused_preprocess_kernel
from repro.kernels.fused_preprocess.ref import fused_preprocess_ref


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("crop", "factor", "mean", "std",
                                             "grey", "out_dtype", "interpret"))
def fused_preprocess(frames: jax.Array, *, crop: Tuple[int, int, int, int],
                     factor: int = 1,
                     mean: Tuple[float, ...] = (0.5, 0.5, 0.5),
                     std: Tuple[float, ...] = (0.25, 0.25, 0.25),
                     grey: bool = False, out_dtype=jnp.float32,
                     interpret: bool = False) -> jax.Array:
    if interpret or _use_pallas():
        return fused_preprocess_kernel(
            frames, crop=crop, factor=factor, mean=mean, std=std, grey=grey,
            out_dtype=out_dtype, interpret=interpret)
    return fused_preprocess_ref(frames, crop=crop, factor=factor, mean=mean,
                                std=std, grey=grey, out_dtype=out_dtype)
