"""Virtualized device-state store for cohort-sampled training
(DESIGN.md §11).

The stacked engine materializes every device's personal state as
(M, N, ...) leaves each round, so memory — not compute — caps the
population. This module inverts that layout: the full population lives
in a :class:`DeviceStateStore` (stacked leaves keyed by (team, device),
shardable over the mesh `data` axis via
:func:`repro.sharding.specs.store_pspecs`), and each round the engine
gathers only the sampled cohort `(M, n_cohort)` in-graph, runs the
unchanged algorithm round at cohort width, and scatters the updated
rows back. Personal params, error-feedback ``CommState`` residuals and
probe state all ride the same gather, selected per-algorithm by
``FLAlgorithm.device_axes``.

Cohort sampling is without replacement and index maps are sorted
(:func:`repro.core.participation.sample_cohort`), so ``scatter ∘
gather`` is an exact round-trip: non-sampled rows are bit-unchanged and
sampled rows carry exactly the round's update — the property
tests/test_cohort_store.py pins. With ``cohort == n`` the index map is
``arange(n)`` and the whole machinery degenerates to an identity copy,
which is why the full-population path stays bit-identical.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Tuple

import jax

__all__ = ["DeviceStateStore", "gather_cohort", "scatter_cohort",
           "split_device_state"]


def gather_cohort(tree, idx):
    """Materialize the cohort rows of a device-tier pytree.

    tree: leaves stacked (M, N, ...); idx: (M, C) i32 per-team device
    indices. Returns the same structure with (M, C, ...) leaves —
    ``leaf[t, idx[t]]`` per team, as one in-graph vmapped take.
    """
    def take(leaf):
        return jax.vmap(lambda row, i: row[i])(leaf, idx)
    with jax.named_scope("store.gather"):
        return jax.tree.map(take, tree)


def scatter_cohort(tree, idx, update):
    """Write cohort rows back into a device-tier pytree.

    Inverse of :func:`gather_cohort` for sampled rows: returns ``tree``
    with ``leaf[t, idx[t]] <- update_leaf[t]`` per team and every
    non-sampled row untouched. ``idx`` rows are distinct (sampling is
    without replacement), so the scatter is unambiguous.
    """
    def put(leaf, up):
        return jax.vmap(lambda row, i, u: row.at[i].set(u))(leaf, idx, up)
    with jax.named_scope("store.scatter"):
        return jax.tree.map(put, tree, update)


def split_device_state(algo, state, m: int, n: int
                       ) -> Tuple[tuple, tuple, Callable]:
    """Split an algorithm state into (device-tier leaves, resident rest).

    Flags come from ``algo.device_axes(state, m, n)``; ``n`` is the
    width of the device axis *in this state* — the population when
    splitting the resident store, the cohort size when splitting a
    post-round cohort state.

    Returns ``(dev, rest, merge)``: two leaf tuples and a closure
    reassembling the original structure, so the engine can carry the
    store and the resident tiers separately through the scan and
    rebuild full states at eval boundaries.
    """
    leaves, treedef = jax.tree.flatten(state)
    flags = jax.tree.leaves(algo.device_axes(state, m, n))
    if len(flags) != len(leaves):
        raise ValueError(
            f"device_axes returned {len(flags)} flags for "
            f"{len(leaves)} state leaves ({algo.name})")
    flags = tuple(bool(f) for f in flags)
    dev = tuple(l for l, f in zip(leaves, flags) if f)
    rest = tuple(l for l, f in zip(leaves, flags) if not f)

    def merge(dev_leaves, rest_leaves):
        """Reassemble a full state pytree from the two leaf tuples."""
        di, ri = iter(dev_leaves), iter(rest_leaves)
        return jax.tree.unflatten(
            treedef, [next(di) if f else next(ri) for f in flags])

    return dev, rest, merge


@jax.tree_util.register_pytree_node_class
@dataclass
class DeviceStateStore:
    """The resident population's device-tier state: a pytree of stacked
    (M, N, ...) leaves keyed by (team, device), carried through the
    engine's scan while only gathered cohorts are ever materialized at
    round width. ``m``/``n`` are static pytree aux data, so stores nest
    in scan carries and vmap over a sweep axis like any other state.
    """
    tree: Any
    m: int
    n: int

    def tree_flatten(self):
        """Pytree protocol: leaves are the store tree, (m, n) is aux."""
        return (self.tree,), (self.m, self.n)

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Pytree protocol: rebuild from ((tree,), (m, n))."""
        return cls(children[0], *aux)

    def gather(self, idx):
        """Cohort view: :func:`gather_cohort` over the store tree."""
        return gather_cohort(self.tree, idx)

    def scatter(self, idx, update) -> "DeviceStateStore":
        """New store with cohort rows replaced by ``update``
        (:func:`scatter_cohort`); non-sampled rows bit-unchanged."""
        return DeviceStateStore(scatter_cohort(self.tree, idx, update),
                                self.m, self.n)

    def pspecs(self, *, sweep: bool = False):
        """PartitionSpecs sharding the population axis over the mesh
        `data` axis (:func:`repro.sharding.specs.store_pspecs`)."""
        from repro.sharding.specs import store_pspecs
        return store_pspecs(self.tree, m=self.m, population=self.n,
                            sweep=sweep)
