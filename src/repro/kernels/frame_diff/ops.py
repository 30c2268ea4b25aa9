"""Public frame-diff op."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.frame_diff.kernel import frame_diff_kernel
from repro.kernels.frame_diff.ref import frame_diff_ref


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("regions", "interpret"))
def frame_diff(cur: jax.Array, prev: jax.Array, *, regions=(4, 4),
               interpret: bool = False) -> jax.Array:
    if interpret or _use_pallas():
        return frame_diff_kernel(cur, prev, regions=regions,
                                 interpret=interpret)
    return frame_diff_ref(cur, prev, regions=regions)
