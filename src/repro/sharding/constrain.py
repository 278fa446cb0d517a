"""Mesh-aware activation sharding constraints (MaxText-style).

``constrain(x, "batch", None, "model")`` pins an intermediate's sharding
when tracing happens under a mesh made current by ``jax.set_mesh``, and
is a no-op otherwise (CPU unit tests, paper-scale FL sims). Logical names:

  * "batch" -> every batch-ish axis present in the mesh ("pod", "data")
  * "model" -> the tensor/expert-parallel axis
  * "data"  -> the FSDP axis alone

The critical use is scan carries (online-softmax accumulators, SSM/WKV
states): their zeros-init has no sharding preference, and without a
constraint GSPMD can keep the whole carry replicated, exploding the
backward-pass residuals (observed: 150+ GiB/device before, ~2 GiB after).
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def _active_mesh():
    """The mesh ``jax.set_mesh`` made current, or None outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _resolve(axis, mesh_axes):
    if axis is None:
        return None
    if axis == "batch":
        got = tuple(a for a in ("pod", "data") if a in mesh_axes)
        return got if got else None
    if isinstance(axis, (tuple, list)):
        got = tuple(a for a in axis if a in mesh_axes)
        return got if got else None
    return axis if axis in mesh_axes else None


def _axis_size(mesh, axes):
    if axes is None:
        return 1
    if isinstance(axes, tuple):
        out = 1
        for a in axes:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axes]


def constrain(x, *spec):
    """Apply a logical PartitionSpec if a mesh is active; no-op otherwise.

    Axes that do not divide the corresponding dim are dropped (e.g. the
    seq-dim constraint on a decode step's single token)."""
    mesh = _active_mesh()
    if mesh is None:
        return x
    names = mesh.axis_names
    resolved = []
    for dim, s in zip(x.shape, spec):
        axes = _resolve(s, names)
        if axes is not None and dim % _axis_size(mesh, axes) != 0:
            axes = None
        resolved.append(axes)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved)))


def constrain_tree(tree, specs):
    """specs: pytree of tuples matching tree."""
    return jax.tree.map(lambda x, s: constrain(x, *s), tree, specs,
                        is_leaf=lambda v: isinstance(v, tuple))
