"""Public fused-prefix op: one device pass for a plan's whole prefix.

Dispatch follows the package convention: the Pallas kernel on TPU (or in
``interpret`` mode for tests), the pure-jnp oracle as the CPU path.  The
CPU oracle is itself a single XLA program when called under an outer
``jax.jit`` (nested jits inline), so both backends give the streaming
tier one compiled dispatch per micro-batch; ``FusedPrefixOp``
(``repro.streaming.fused``) is the wrapper that composes this with the
detector forward and owns the host-side mask/state logic.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.fused_prefix.kernel import fused_prefix_kernel
from repro.kernels.fused_prefix.ref import fused_prefix_ref


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def fused_prefix(frames: jax.Array, prevs=None, proj=None, *, spec,
                 interpret: bool = False):
    """frames (B, C, H, W), prevs same shape (diff stage only), proj
    (D, EMB_DIM) f32 (signature stage only); ``spec`` is the static
    stage tuple documented in ``ref.fused_prefix_ref``.  Returns
    ``(d, fracs, x, feats, emb)``."""
    if interpret or _use_pallas():
        return fused_prefix_kernel(
            frames, prevs, proj, spec=spec,
            interpret=interpret)
    return fused_prefix_ref(frames, prevs, proj, spec=spec)
