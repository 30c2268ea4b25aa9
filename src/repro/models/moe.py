"""Mixture-of-Experts FFN with expert parallelism over the "model" mesh axis.

Communication pattern (chosen for the production mesh — see DESIGN.md §4):
activations arriving at the MoE layer are sharded over the data axes and
*replicated* over "model" (they exit the attention TP all-reduce that way).
Each model-rank therefore routes all of its local tokens itself, computes
only its *local slice of experts* on a capacity-bounded dispatch buffer, and
the partial outputs are psum'd over "model" — one all-reduce of (tokens × d)
per MoE layer, the same collective class as a TP MLP.  No all-to-all is
needed because tokens never move between data ranks.

Expert weights are additionally sharded over the data axis (ZeRO-3); they are
all-gathered over "data" inside the shard_map right before use.

Capacity routing: per model-rank, each expert takes at most
``C = ceil(top_k · T_loc · capacity_factor / E)`` tokens (overflow dropped —
standard Switch/GShard semantics).  Router runs in fp32 with z-loss + load-
balance aux loss.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.common.config import MoEConfig
from repro.common.sharding import (
    current_mesh,
    dp_axis_names,
    logical_to_mesh,
)
from repro.common.utils import ceil_div
from repro.models.param import ParamSpec


def moe_spec(d_model: int, moe: MoEConfig) -> Dict[str, ParamSpec]:
    e, f = moe.n_experts, moe.d_ff_expert
    spec = {
        "router": ParamSpec((d_model, e), (None, None), "small"),
        "w_in": ParamSpec((e, d_model, f),
                          ("experts", "expert_fsdp", "expert_ff")),
        "w_gate": ParamSpec((e, d_model, f),
                            ("experts", "expert_fsdp", "expert_ff")),
        "w_out": ParamSpec((e, f, d_model),
                           ("experts", "expert_ff", "expert_fsdp")),
    }
    if moe.n_shared_experts:
        fs = f * moe.n_shared_experts
        spec["shared_in"] = ParamSpec((d_model, fs), ("fsdp", "mlp"))
        spec["shared_gate"] = ParamSpec((d_model, fs), ("fsdp", "mlp"))
        spec["shared_out"] = ParamSpec((fs, d_model), ("mlp", "fsdp"))
    return spec


def _route(router_w: jax.Array, x2d: jax.Array, moe: MoEConfig):
    """Top-k routing. x2d (T, d) -> (idx (T,k), weights (T,k), aux losses)."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, moe.top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch): E * sum(frac_tokens * frac_probs)
    e = logits.shape[-1]
    onehot = jax.nn.one_hot(idx[:, 0], e)  # top-1 proxy for load
    frac_tokens = onehot.mean(0)
    frac_probs = probs.mean(0)
    aux = moe.aux_loss_coef * e * jnp.sum(frac_tokens * frac_probs)
    z = moe.router_z_coef * jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1)))
    return idx, weights, aux + z


def _expert_ffn(w_in, w_gate, w_out, xb: jax.Array) -> jax.Array:
    """xb (E_loc, C, d) -> (E_loc, C, d)."""
    dtype = xb.dtype
    h = jnp.einsum("ecd,edf->ecf", xb, w_in.astype(dtype))
    g = jnp.einsum("ecd,edf->ecf", xb, w_gate.astype(dtype))
    h = jax.nn.silu(h) * g
    return jnp.einsum("ecf,efd->ecd", h, w_out.astype(dtype))


def _moe_local(x2d, router_w, w_in, w_gate, w_out, moe: MoEConfig,
               e_start: jax.Array, e_local: int, capacity: int,
               model_axis: Optional[str], fsdp_axis,
               x_replicated: bool = False):
    """Per-(data,model)-shard MoE body. x2d (T_loc, d) replicated over model."""
    t, d = x2d.shape
    e = moe.n_experts
    idx, weights, aux = _route(router_w, x2d, moe)

    # Position of each (token, k) assignment within its expert's capacity.
    flat_e = idx.reshape(-1)                      # (T*k,)
    flat_w = weights.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), moe.top_k)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)      # (T*k, E)
    pos_in_e = jnp.cumsum(onehot, axis=0) * onehot           # 1-based
    pos = jnp.max(pos_in_e, axis=-1) - 1                     # (T*k,)
    keep = (pos >= 0) & (pos < capacity)

    # Only this rank's experts.
    local = (flat_e >= e_start) & (flat_e < e_start + e_local) & keep
    slot = jnp.where(local, (flat_e - e_start) * capacity + pos, e_local * capacity)
    # dispatch buffer (E_loc*C + 1 overflow row, d)
    buf = jnp.zeros((e_local * capacity + 1, d), x2d.dtype)
    buf = buf.at[slot].add(jnp.where(local[:, None], x2d[flat_tok], 0))
    xb = buf[:-1].reshape(e_local, capacity, d)

    import os

    if fsdp_axis and x_replicated and \
            os.environ.get("REPRO_MOE_PARTIAL") == "1":
        # §Perf H3: keep expert weights ZeRO-sharded and exchange
        # *activations* instead — contract each rank's d-slice, psum the
        # (E_loc, C, f) partials, and all-gather the (E_loc, C, d/dp)
        # output slices.  ONLY valid when x (and hence the dispatch buffer)
        # is replicated over the fsdp axis — i.e. the decode/serving path
        # (batch-sharded training buffers differ per rank; the psum would
        # mix tokens).  Activation traffic is O(C·f) per token step vs the
        # baseline's O(params_bytes/16) weight gathers — the long-context
        # decode hillclimb's 45x collective reduction.
        didx = jax.lax.axis_index(fsdp_axis[0])   # single fsdp axis ("data")
        dloc = w_in.shape[1]                      # local d rows
        xb_slice = jax.lax.dynamic_slice_in_dim(xb, didx * dloc, dloc,
                                                axis=2)
        dtype = xb.dtype
        h = jnp.einsum("ecd,edf->ecf", xb_slice, w_in.astype(dtype))
        g = jnp.einsum("ecd,edf->ecf", xb_slice, w_gate.astype(dtype))
        hg = jax.lax.psum(jnp.stack([h, g]), fsdp_axis[0])
        act = jax.nn.silu(hg[0]) * hg[1]
        y_loc = jnp.einsum("ecf,efd->ecd", act, w_out.astype(dtype))
        yb = jax.lax.all_gather(y_loc, fsdp_axis[0], axis=2, tiled=True)
        yb = yb.reshape(e_local * capacity, d)
    else:
        # baseline: gather ZeRO-sharded expert weights over the data axes
        if fsdp_axis:
            w_in = jax.lax.all_gather(w_in, fsdp_axis, axis=1, tiled=True)
            w_gate = jax.lax.all_gather(w_gate, fsdp_axis, axis=1, tiled=True)
            w_out = jax.lax.all_gather(w_out, fsdp_axis, axis=2, tiled=True)
        yb = _expert_ffn(w_in, w_gate, w_out, xb).reshape(
            e_local * capacity, d)
    yb = jnp.concatenate([yb, jnp.zeros((1, d), yb.dtype)], axis=0)
    contrib = yb[slot] * (flat_w * local.astype(flat_w.dtype))[:, None].astype(yb.dtype)
    y = jnp.zeros((t, d), x2d.dtype).at[flat_tok].add(contrib)

    if model_axis is not None:
        y = jax.lax.psum(y, model_axis)
        aux = aux  # router identical on every model rank; no psum needed
    return y, aux


def apply_moe(params: Dict[str, Any], x: jax.Array, moe: MoEConfig,
              batch_sharded: bool = True) -> Tuple[jax.Array, jax.Array]:
    """x (B,S,d) -> (y (B,S,d), aux_loss scalar)."""
    mesh = current_mesh()
    b, s, d = x.shape
    e = moe.n_experts

    if mesh is None or "model" not in mesh.axis_names:
        # single-device path (smoke tests / streaming models)
        x2d = x.reshape(b * s, d)
        cap = max(1, ceil_div(moe.top_k * b * s, e))
        cap = int(cap * moe.capacity_factor) + 1
        y, aux = _moe_local(
            x2d, params["router"], params["w_in"], params["w_gate"],
            params["w_out"], moe, jnp.int32(0), e, cap, None, ())
        y = y.reshape(b, s, d)
    else:
        tp = mesh.shape["model"]
        dp_axes = dp_axis_names(mesh) if batch_sharded else ()
        dp = int(np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else 1
        t_loc = (b * s) // dp
        cap = max(1, int(ceil_div(moe.top_k * t_loc, e) * moe.capacity_factor) + 1)
        e_local = e // tp
        fsdp_axes = tuple(a for a in ("data",) if a in mesh.axis_names)

        batch_spec = P(dp_axes if batch_sharded else None)
        x_spec = P(*(batch_spec + P(None, None)))

        def body(x3d, router_w, w_in, w_gate, w_out):
            t_rank = jax.lax.axis_index("model")
            e_start = t_rank * e_local
            x2d = x3d.reshape(-1, d)
            y, aux = _moe_local(x2d, router_w, w_in, w_gate, w_out, moe,
                                e_start, e_local, cap, "model", fsdp_axes,
                                x_replicated=not batch_sharded)
            # aux identical across model ranks; average over data ranks happens
            # outside via mean of replicated value
            return y.reshape(x3d.shape), aux

        y, aux = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                x_spec,
                P(None, None),
                P("model", fsdp_axes if fsdp_axes else None, None),
                P("model", fsdp_axes if fsdp_axes else None, None),
                P("model", None, fsdp_axes if fsdp_axes else None),
            ),
            out_specs=(x_spec, P()),
            check_vma=False,
        )(x, params["router"], params["w_in"], params["w_gate"],
          params["w_out"])

    if moe.n_shared_experts:
        dtype = x.dtype
        h = jnp.einsum("bsd,df->bsf", x, params["shared_in"].astype(dtype))
        g = jnp.einsum("bsd,df->bsf", x, params["shared_gate"].astype(dtype))
        y = y + jnp.einsum("bsf,fd->bsd", jax.nn.silu(h) * g,
                           params["shared_out"].astype(dtype))
    return y, aux
