"""Pallas TPU kernel: fused int8 stochastic quantize/pack (comm uplink).

Unfused, XLA materializes |v|, the row-max, v/scale, the noised round and
the dequantized echo as separate HBM round trips. Fused, v and the noise
stream through VMEM once and three outputs (packed q, per-row scales, the
dequantized value the simulator aggregates) are written in the same pass:
the bandwidth floor for the compression step that runs K times per global
round on every device's delta. Blocks are (block_rows, 128) — lane-aligned
for the VPU; arrays are flattened and padded to a multiple of 128 by the
wrapper, matching ref.py exactly so interpret mode is bit-comparable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _quant_kernel(v_ref, n_ref, q_out, s_out, dq_out):
    v = v_ref[...].astype(jnp.float32)
    u = n_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(v), axis=1, keepdims=True)
    scale = jnp.maximum(absmax * (1.0 / 127.0), 1e-12)
    q = jnp.clip(jnp.floor(v / scale + u), -127.0, 127.0)
    q_out[...] = q.astype(jnp.int8)
    s_out[...] = scale
    dq_out[...] = (q * scale).astype(dq_out.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def quantize_int8_flat(v, noise, *, block_rows: int = 256,
                       interpret: bool = False):
    """1-D inputs (already flat). Returns (q (size,) i8, scales (rows,) f32,
    dq (size,) of v.dtype)."""
    (size,) = v.shape
    rows = pl.cdiv(size, LANES)
    pad = rows * LANES - size

    def prep(x):
        if pad:
            x = jnp.pad(x, (0, pad))
        return x.reshape(rows, LANES)

    v2 = prep(v.astype(jnp.float32))
    n2 = prep(noise.astype(jnp.float32))
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    s_spec = pl.BlockSpec((block_rows, 1), lambda i: (i, 0))
    q, s, dq = pl.pallas_call(
        _quant_kernel, grid=grid,
        in_specs=[spec, spec],
        out_specs=[spec, s_spec, spec],
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rows, LANES), v.dtype)],
        interpret=interpret, name="quantize_int8_flat",
    )(v2, n2)
    return q.reshape(-1)[:size], s.reshape(-1), dq.reshape(-1)[:size]
