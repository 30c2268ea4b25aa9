"""Logical-axis sharding: one rules table maps logical tensor axes to mesh axes.

The production mesh is ``("data","model")`` single-pod or
``("pod","data","model")`` multi-pod (see launch/mesh.py).  Model code only
ever names *logical* axes; the rules below translate them, dropping mesh axes
that are absent (so the same model runs on a 1-device test mesh, a single-pod
mesh, and a multi-pod mesh unchanged).

Per-cell overrides (e.g. long_500k shards the KV sequence over "data" and
replicates the batch) are applied with ``rules_scope``.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AxisVal = Union[None, str, Tuple[str, ...]]

# logical axis -> mesh axes (tuple entries mean "sharded over both, major first")
DEFAULT_RULES: Dict[str, AxisVal] = {
    "batch": ("pod", "data"),      # global batch -> DP over pod+data
    "seq": None,                   # activations: sequence replicated by default
    "kv_seq": None,                # KV cache sequence (sharded for long_500k)
    "embed": None,                 # activation d_model
    "fsdp": ("data",),             # weight rows: ZeRO-3 over the data axis
    "vocab": ("model",),
    "heads": ("model",),           # attention q heads (TP)
    "kv_heads": ("model",),
    "head_dim": None,
    "mlp": ("model",),             # FFN hidden (TP)
    "experts": ("model",),         # MoE expert parallelism
    "expert_fsdp": ("data",),      # expert weight d-rows (ZeRO-3; kept even
                                   # when dense "fsdp" is overridden — the
                                   # MoE shard_map handles the exchange)
    "expert_ff": None,
    "ssm_heads": ("model",),       # mamba heads (TP)
    "ssm_state": None,
    "conv": None,
    "layers": None,                # scan-stacked leading dim
    "frames": None,
    "pixels": None,
}

_local = threading.local()


def _stack() -> list:
    if not hasattr(_local, "stack"):
        _local.stack = []
    return _local.stack


def current_rules() -> Dict[str, AxisVal]:
    rules = dict(DEFAULT_RULES)
    for override in _stack():
        rules.update(override)
    return rules


@contextlib.contextmanager
def rules_scope(**overrides: AxisVal):
    """Temporarily override logical->mesh rules (e.g. for decode cells)."""
    _stack().append(overrides)
    try:
        yield
    finally:
        _stack().pop()


# Global mesh used by shard_constraint / shard_map blocks. ``None`` disables
# constraints entirely (pure single-device smoke-test mode).
_MESH: Optional[Mesh] = None


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _MESH
    _MESH = mesh


def current_mesh() -> Optional[Mesh]:
    return _MESH


@contextlib.contextmanager
def mesh_scope(mesh: Optional[Mesh]):
    global _MESH
    prev = _MESH
    _MESH = mesh
    try:
        if mesh is not None:
            with mesh:
                yield
        else:
            yield
    finally:
        _MESH = prev


def _filter_axes(val: AxisVal, mesh: Mesh) -> AxisVal:
    names = set(mesh.axis_names)
    if val is None:
        return None
    if isinstance(val, str):
        return val if val in names else None
    kept = tuple(a for a in val if a in names)
    if not kept:
        return None
    return kept if len(kept) > 1 else kept[0]


def logical_to_mesh(
    axes: Sequence[Optional[str]], mesh: Optional[Mesh] = None
) -> PartitionSpec:
    """Translate a tuple of logical axis names into a PartitionSpec."""
    mesh = mesh or current_mesh()
    if mesh is None:
        return PartitionSpec()
    rules = current_rules()
    used: set = set()
    out = []
    for ax in axes:
        val = rules.get(ax) if ax is not None else None
        val = _filter_axes(val, mesh)
        # a mesh axis may appear at most once in a spec
        if isinstance(val, tuple):
            val = tuple(a for a in val if a not in used) or None
            if isinstance(val, tuple) and len(val) == 1:
                val = val[0]
        if isinstance(val, str) and val in used:
            val = None
        if isinstance(val, tuple):
            used.update(val)
        elif isinstance(val, str):
            used.add(val)
        out.append(val)
    return PartitionSpec(*out)


def shard_constraint(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op without a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = logical_to_mesh(axes, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(axes: Sequence[Optional[str]], mesh: Optional[Mesh] = None):
    mesh = mesh or current_mesh()
    assert mesh is not None
    return NamedSharding(mesh, logical_to_mesh(axes, mesh))


def param_sharding_tree(axes_tree: Any, mesh: Optional[Mesh] = None) -> Any:
    """Map a tree of logical-axes tuples to NamedShardings."""
    mesh = mesh or current_mesh()
    return jax.tree_util.tree_map(
        lambda axes: named_sharding(axes, mesh),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(a is None or isinstance(a, str) for a in x),
    )


def dp_axis_names(mesh: Optional[Mesh] = None) -> Tuple[str, ...]:
    """All mesh axes that carry data parallelism (everything but 'model')."""
    mesh = mesh or current_mesh()
    if mesh is None:
        return ()
    return tuple(a for a in mesh.axis_names if a != "model")


def axis_size(name: str, mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or current_mesh()
    if mesh is None or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def tp_size(mesh: Optional[Mesh] = None) -> int:
    return axis_size("model", mesh)


def dp_size(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or current_mesh()
    if mesh is None:
        return 1
    n = 1
    for a in dp_axis_names(mesh):
        n *= mesh.shape[a]
    return n
