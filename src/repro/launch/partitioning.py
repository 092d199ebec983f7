"""Logical-axis partitioning context (MaxText-style).

Model code annotates tensors with *logical* axis names; the launcher
installs a mesh + rules mapping logical names to mesh axes.  Outside any
context (unit tests, single-device smoke runs) every annotation is a no-op.

Rules drop mappings that don't divide evenly (e.g. 8 KV heads on a 16-wide
``model`` axis fall back to replicated), which keeps one config portable
across meshes.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "default_rules", "mesh_context", "logical_constraint", "spec_for",
    "sharding_for", "tree_shardings", "current_mesh", "current_batch_shards",
    "current_batch_axes", "auto_axis_types",
]

AxisName = Union[str, Tuple[str, ...], None]

_state = threading.local()


def auto_axis_types(n_axes: int) -> Dict[str, tuple]:
    """``axis_types=(AxisType.Auto, ...)`` kwargs for ``jax.make_mesh``.

    ``jax.make_mesh`` builds Explicit axes unless told otherwise, and the
    partitioning rules here rely on ``with_sharding_constraint`` under
    Auto axes.  Splat the result: ``jax.make_mesh(shape, names,
    **auto_axis_types(len(names)))``.
    """
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def default_rules(mesh: Mesh) -> Dict[str, AxisName]:
    """Logical-axis → mesh-axis rules for the production meshes."""
    axes = mesh.axis_names
    batch: AxisName = ("pod", "data") if "pod" in axes else ("data",)
    return {
        "batch": batch,
        "vocab": "model",
        "embed_fsdp": "data",    # FSDP within a pod; never across pods
        "heads": "model",        # tensor parallel
        "ff": "model",
        "expert": "model",       # expert parallel
        "ssm_inner": "model",
        "q_heads": "model",
        "kv_heads": "model",
        "kv_seq": "model",       # flash-decoding style cache sharding
        "seq_sp": "model",       # sequence-parallel saved activations
        "layer": None,
        "seq": None,
    }


@contextlib.contextmanager
def mesh_context(mesh: Mesh, rules: Optional[Dict[str, AxisName]] = None):
    prev = getattr(_state, "ctx", None)
    _state.ctx = (mesh, rules or default_rules(mesh))
    try:
        with mesh:
            yield
    finally:
        _state.ctx = prev


def current_mesh() -> Optional[Mesh]:
    ctx = getattr(_state, "ctx", None)
    return ctx[0] if ctx else None


def current_batch_axes() -> Tuple[str, ...]:
    """Mesh axes the 'batch' logical axis maps to (empty w/o context)."""
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        return ()
    mesh, rules = ctx
    target = rules.get("batch")
    if target is None:
        return ()
    names = (target,) if isinstance(target, str) else tuple(target)
    return tuple(n for n in names if n in mesh.axis_names)


def current_batch_shards() -> int:
    """Number of shards the 'batch' logical axis maps to (1 w/o context)."""
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        return 1
    mesh = ctx[0]
    size = 1
    for n in current_batch_axes():
        size *= mesh.shape[n]
    return size


def _resolve(axis: Optional[str], dim: int, mesh: Mesh,
             rules: Dict[str, AxisName], used: set) -> AxisName:
    if axis is None:
        return None
    target = rules.get(axis)
    if target is None:
        return None
    names = (target,) if isinstance(target, str) else tuple(target)
    names = tuple(n for n in names if n in mesh.axis_names and n not in used)
    if not names:
        return None
    size = 1
    for n in names:
        size *= mesh.shape[n]
    if dim % size != 0:
        return None  # non-divisible -> replicate (portable configs)
    used.update(names)
    return names if len(names) > 1 else names[0]


def spec_for(axes: Sequence[Optional[str]], shape: Sequence[int],
             mesh: Mesh, rules: Dict[str, AxisName]) -> P:
    used: set = set()
    return P(*[_resolve(a, d, mesh, rules, used)
               for a, d in zip(axes, shape)])


def logical_constraint(x, *axes: Optional[str]):
    """with_sharding_constraint by logical axis names (no-op w/o context)."""
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = spec_for(axes, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def sharding_for(axes: Sequence[Optional[str]], shape: Sequence[int],
                 mesh: Mesh, rules: Optional[Dict[str, AxisName]] = None
                 ) -> NamedSharding:
    rules = rules or default_rules(mesh)
    return NamedSharding(mesh, spec_for(axes, shape, mesh, rules))


def tree_shardings(axes_tree, shapes_tree, mesh: Mesh,
                   rules: Optional[Dict[str, AxisName]] = None):
    """NamedSharding tree from (logical-axes tree, ShapeDtypeStruct tree)."""
    rules = rules or default_rules(mesh)
    return jax.tree.map(
        lambda axes, sds: sharding_for(axes, sds.shape, mesh, rules),
        axes_tree, shapes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x),
    )
