"""Logical-axis sharding (MaxText-style, minimal).

Every parameter (and cache buffer) carries a tuple of *logical* axis names —
one per dimension — via :class:`LogicalParam`, a pytree node whose axes are
**static treedef metadata**.  That makes ``jax.eval_shape(init)`` work: the
dry-run derives full sharding trees for 100B+ parameter models without ever
allocating them.

A :class:`Rules` mapping translates logical names to mesh axes per run mode
(train vs serve, single- vs multi-pod).  Specs are derived shape-aware: a
mesh axis that does not divide the dimension, or that is already consumed by
an earlier dimension of the same tensor, falls back to replication (never a
compile error).
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@jax.tree_util.register_pytree_node_class
class LogicalParam:
    """array (or ShapeDtypeStruct) + logical axes (static, one per dim)."""

    __slots__ = ("value", "axes")

    def __init__(self, value: Any, axes: Sequence[Optional[str]]):
        self.value = value
        self.axes = tuple(axes)

    def tree_flatten(self):
        return (self.value,), self.axes

    @classmethod
    def tree_unflatten(cls, axes, children):
        return cls(children[0], axes)

    def __repr__(self):
        shape = getattr(self.value, "shape", None)
        return f"LogicalParam(shape={shape}, axes={self.axes})"


def is_lp(x) -> bool:
    return isinstance(x, LogicalParam)


def param(key, shape, axes, dtype, scale: float = 0.02,
          init: str = "normal") -> LogicalParam:
    assert len(shape) == len(axes), (shape, axes)
    if init == "normal":
        v = jax.random.normal(key, shape, dtype) * jnp.asarray(scale, dtype)
    elif init == "zeros":
        v = jnp.zeros(shape, dtype)
    elif init == "ones":
        v = jnp.ones(shape, dtype)
    else:
        raise ValueError(init)
    return LogicalParam(v, tuple(axes))


def values_of(tree):
    """LogicalParam tree -> plain value tree (same dict structure)."""
    return jax.tree.map(lambda p: p.value if is_lp(p) else p, tree,
                        is_leaf=is_lp)


def split_tree(tree):
    return values_of(tree), tree  # values + (the LP tree doubles as axes)


def spec_for(shape: Sequence[int], axes: Sequence[Optional[str]],
             rules: dict, mesh_shape: dict) -> P:
    """Shape-aware PartitionSpec: divisibility + no-mesh-axis-reuse."""
    used: set = set()
    parts = []
    for dim, name in zip(shape, axes):
        mapped = rules.get(name) if name is not None else None
        if mapped is None:
            parts.append(None)
            continue
        mesh_axes = (mapped,) if isinstance(mapped, str) else tuple(mapped)
        mesh_axes = tuple(a for a in mesh_axes
                          if a not in used and a in mesh_shape)
        size = 1
        keep = []
        for a in mesh_axes:
            if dim % (size * mesh_shape[a]) == 0:
                keep.append(a)
                size *= mesh_shape[a]
        if not keep:
            parts.append(None)
        else:
            used.update(keep)
            parts.append(tuple(keep) if len(keep) > 1 else keep[0])
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def specs_of(lp_tree, rules: dict, mesh: Mesh):
    """LogicalParam tree -> PartitionSpec tree (same structure, P leaves)."""
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    return jax.tree.map(
        lambda p: spec_for(p.value.shape, p.axes, rules, mesh_shape),
        lp_tree, is_leaf=is_lp)


def shardings_of(lp_tree, rules: dict, mesh: Mesh):
    specs = specs_of(lp_tree, rules, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def like_shardings(values_tree, spec, mesh: Mesh):
    """Uniform sharding for a whole tree (e.g. replicated scalars)."""
    return jax.tree.map(lambda _: NamedSharding(mesh, spec), values_tree)


# -------------------- in-function sharding constraints ----------------------

def constrain(x, axes: Sequence[Optional[str]], rules: Optional[dict]):
    """with_sharding_constraint by logical axes.

    No-op when no rules are given (smoke tests run un-meshed on one
    device).  Rules without a mesh set by ``jax.set_mesh`` (or an
    enclosing ``shard_map``) are an error: silently dropping the
    constraint would hide a lost mesh.  Inside a partial-manual shard_map,
    axes that are Manual (e.g. the deferred-sync data axis) are dropped
    from the spec — constraints only apply to the remaining auto axes.
    """
    if rules is None:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        raise ValueError("sharding rules given but no mesh is set; run "
                         "under `with jax.set_mesh(mesh):`")
    mesh_shape = {name: size for name, size, t in
                  zip(mesh.axis_names, mesh.axis_sizes, mesh.axis_types)
                  if t != jax.sharding.AxisType.Manual}
    spec = spec_for(x.shape, axes, rules, mesh_shape)
    return jax.lax.with_sharding_constraint(x, spec)
