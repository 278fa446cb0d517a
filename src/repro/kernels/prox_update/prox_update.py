"""Pallas TPU kernel: fused PerMFL prox-SGD device step (paper eq. 4).

Unfused, XLA issues (read theta, read grad, read anchor, write theta) plus a
temporary for (theta - anchor): ~5 HBM round trips of the parameter block.
Fused, each of theta/grad/anchor/momentum streams through VMEM exactly once:
1 write + 3..4 reads, the bandwidth floor. Blocks are (block_rows, 128) —
lane-aligned for the VPU; arrays are flattened and padded to a multiple of
128 by the wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _prox_kernel(s_ref, t_ref, g_ref, a_ref, m_ref, t_out, m_out, *,
                 momentum, weight_decay):
    # alpha/lam ride in SMEM as a (1, 2) scalar operand: they are sweepable
    # hyperparameters (run_sweep vmaps grids of them), so they must be
    # runtime values, not compile-time constants. momentum/weight_decay
    # select the kernel branch and stay static.
    alpha = s_ref[0, 0]
    lam = s_ref[0, 1]
    t = t_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)
    upd = g + lam * (t - a) + weight_decay * t
    if momentum > 0.0:
        mb = m_ref[...].astype(jnp.float32)
        mb = momentum * mb + upd
        m_out[...] = mb.astype(m_out.dtype)
        upd = mb
    else:
        m_out[...] = m_ref[...]
    t_out[...] = (t - alpha * upd).astype(t_out.dtype)


@functools.partial(jax.jit, static_argnames=(
    "momentum", "weight_decay", "block_rows", "interpret"))
def prox_sgd_flat(theta, grad, anchor, mom_buf, *, alpha, lam,
                  momentum=0.0, weight_decay=0.0, block_rows: int = 256,
                  interpret: bool = False):
    """1-D inputs (already flat). alpha/lam may be traced scalars (they
    enter the kernel via SMEM). Returns (theta_new, mom_new)."""
    (size,) = theta.shape
    rows = pl.cdiv(size, LANES)
    pad = rows * LANES - size
    def prep(x):
        if pad:
            x = jnp.pad(x, (0, pad))
        return x.reshape(rows, LANES)
    t2, g2, a2, m2 = prep(theta), prep(grad), prep(anchor), prep(mom_buf)
    scal = jnp.stack([jnp.asarray(alpha, jnp.float32),
                      jnp.asarray(lam, jnp.float32)]).reshape(1, 2)
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    sspec = pl.BlockSpec((1, 2), lambda i: (0, 0),
                         memory_space=pltpu.SMEM)
    kernel = functools.partial(_prox_kernel, momentum=momentum,
                               weight_decay=weight_decay)
    t_new, m_new = pl.pallas_call(
        kernel, grid=grid,
        in_specs=[sspec, spec, spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(t2.shape, theta.dtype),
                   jax.ShapeDtypeStruct(m2.shape, jnp.float32)],
        interpret=interpret, name="prox_sgd_flat",
    )(scal, t2, g2, a2, m2)
    return t_new.reshape(-1)[:size], m_new.reshape(-1)[:size]
