"""GPipe-style pipeline parallelism over a mesh axis (the "pod" axis).

Inter-pod links (DCN) are slow relative to ICI; point-to-point microbatch
hand-off is the communication pattern that fits them — so the multi-pod mesh
optionally maps its "pod" axis to pipeline stages instead of pure DP.

Implementation: shard_map over the pipeline axis.  Each rank holds one
stage's parameters; microbatches stream through a lax.fori_loop whose body
(a) runs the local stage on its current microbatch and (b) rotates
activations to the next rank with ppermute.  With S stages and M
microbatches the loop runs M + S - 1 ticks (the classic GPipe bubble
S-1/(M+S-1), reported by ``bubble_fraction``).

This module is deliberately model-agnostic: ``stage_fn(stage_params, x)``
is any jittable function (tests drive it with an MLP stack; the LM stack's
period structure slots in the same way by stacking periods per stage).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    axis: str = "pod"
    microbatches: int = 4

    def bubble_fraction(self, n_stages: int) -> float:
        return (n_stages - 1) / (self.microbatches + n_stages - 1)


def gpipe(stage_fn: Callable[[Any, jax.Array], jax.Array], mesh: Mesh,
          cfg: PipelineConfig = PipelineConfig()):
    """Returns pipelined_fn(stage_params, x) -> y.

    stage_params: pytree whose leaves have a leading stage axis sharded over
    ``cfg.axis`` (rank i holds stage i).  x: (batch, ...) replicated over
    ``cfg.axis`` (it is split into microbatches internally).
    """
    axis = cfg.axis
    n_stages = mesh.shape[axis]
    m = cfg.microbatches
    assert m >= n_stages, "microbatches must cover the pipeline depth"

    def local(stage_params, x):
        # stage_params leaves: (1, ...) local slice -> squeeze stage dim
        sp = jax.tree_util.tree_map(lambda l: l[0], stage_params)
        rank = jax.lax.axis_index(axis)
        b = x.shape[0]
        mb = b // m
        xs = x.reshape(m, mb, *x.shape[1:])
        n_ticks = m + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(t, carry):
            buf, out = carry
            # which microbatch does this rank process at tick t?
            idx = t - rank
            active = (idx >= 0) & (idx < m)
            # stage 0 ingests microbatch idx; others use the rotated buffer
            inject = jnp.where(
                jnp.logical_and(rank == 0, active),
                xs[jnp.clip(idx, 0, m - 1)], buf)
            y = stage_fn(sp, inject)
            y = jnp.where(active, y, buf)
            # last stage writes its finished microbatch to the output slot
            done_idx = jnp.clip(idx, 0, m - 1)
            write = jnp.logical_and(rank == n_stages - 1, active)
            out = jax.lax.cond(write,
                               lambda o: o.at[done_idx].set(y),
                               lambda o: o, out)
            # rotate activations to the next stage
            buf = jax.lax.ppermute(y, axis, perm)
            return buf, out

        buf0 = jnp.zeros(xs.shape[1:], x.dtype)
        out0 = jnp.zeros_like(xs)
        _, out = jax.lax.fori_loop(0, n_ticks, tick, (buf0, out0))
        # only the last rank holds real outputs; broadcast via psum of
        # masked contribution
        is_last = (rank == n_stages - 1).astype(out.dtype)
        out = jax.lax.psum(out * is_last, axis)
        return out.reshape(b, *out.shape[2:])

    def pipelined(stage_params, x):
        in_specs = (jax.tree_util.tree_map(lambda _: P(axis), stage_params,
                                           is_leaf=lambda l: hasattr(
                                               l, "shape")),
                    P())
        return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                             out_specs=P(), check_vma=False)(
            stage_params, x)

    return pipelined
