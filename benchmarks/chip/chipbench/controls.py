"""Lower-precision stand-ins for the program, used to show that each
comparison fails a computation one step cheaper than the configuration
states: float32 training redone in bfloat16, an int8 store redone in
int4."""
from __future__ import annotations

import jax.numpy as jnp

LANES = 128


def quantize_rows(a, bits: int):
    """Symmetric round-to-nearest quantization of ``a`` in rows of 128
    elements (one scale per row, as the program's int8 store keeps),
    returned dequantized in ``a``'s shape and dtype."""
    levels = 2 ** (bits - 1) - 1
    flat = a.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % LANES
    rows = jnp.pad(flat, (0, pad)).reshape(-1, LANES)
    scale = jnp.maximum(jnp.max(jnp.abs(rows), axis=1, keepdims=True)
                        / levels, 1e-12)
    q = jnp.clip(jnp.round(rows / scale), -levels, levels)
    return (q * scale).reshape(-1)[:flat.size].reshape(a.shape).astype(a.dtype)
