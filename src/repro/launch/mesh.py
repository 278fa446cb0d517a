"""Production mesh construction.

Single pod: (16, 16) = 256 v5e chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — `pod` is the
DCN-connected axis, which PerMFL's team/global tier structure maps onto
(DESIGN.md §2).

These are FUNCTIONS (not module constants) so importing this module never
touches jax device state — dryrun.py sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init,
and only dryrun does.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: this JAX defaults to
    ``Explicit`` axes, under which sharded scan carries change type
    between iterations; ``Auto`` keeps GSPMD's propagation semantics that
    the engine, sweep and sharding rules are written for."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single-pod (data, model) or multi-pod (pod, data, model) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_sweep_mesh(n_sweep: int, *, n_data: int = 1, n_model: int = 1):
    """(sweep, data, model) mesh for batched hyperparameter/seed sweeps.

    The sweep axis takes the pod (DCN) tier: configs are embarrassingly
    parallel — no cross-config collectives ever cross it — so the slowest
    links carry zero sweep traffic. ``run_sweep`` splits configs over
    the sweep axis under ``shard_map`` and keeps each config's (M, N)
    state whole, so it takes data and model axes of size 1 only
    (DESIGN.md §6).
    """
    return _auto_mesh((n_sweep, n_data, n_model), ("sweep", "data", "model"))


def batch_axes(mesh) -> tuple:
    """The axes the global batch shards over."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def mesh_batch_size(mesh) -> int:
    out = 1
    for a in batch_axes(mesh):
        out *= mesh.shape[a]
    return out


def make_host_mesh(n_data: int = 1, n_model: int = 1,
                   n_sweep: int = None):
    """Tiny mesh over whatever devices exist (CPU tests). Passing
    n_sweep prepends a sweep axis: (sweep, data, model)."""
    if n_sweep is not None:
        return make_sweep_mesh(n_sweep, n_data=n_data, n_model=n_model)
    return _auto_mesh((n_data, n_model), ("data", "model"))


# Hardware constants for the roofline model (TPU v5e)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
