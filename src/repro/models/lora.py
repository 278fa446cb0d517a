"""LoRA adapters (arXiv 2106.09685) on frozen projections.

A projection ``y = x W`` with an adapter becomes
``y = x W + scale * (x A) B`` with ``A`` (in, r) normal of std
1/sqrt(in), ``B`` (r, out) zero and ``scale = alpha / r``, so a fresh
adapter leaves the model unchanged. The frozen ``W`` may be held in
bfloat16: its product takes the activations in ``W``'s dtype and
accumulates in float32. Adapters and their products stay float32.

Adapters here are stacked per device: ``a`` (D, in, r), ``b`` (D, r,
out) against activations (D, tokens, in), so one base matmul serves
every device's tokens while each device applies its own adapter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import frozen_matmul


def lora_init(key, d_in: int, d_out: int, rank: int):
    """One projection's adapter: {"a": (in, r), "b": (r, out)} float32."""
    return {"a": jax.random.normal(key, (d_in, rank), jnp.float32)
            / jnp.sqrt(jnp.float32(d_in)),
            "b": jnp.zeros((rank, d_out), jnp.float32)}


def project(x, w, adapter=None, scale: float = 1.0):
    """x: (D, tokens, in) -> (D, tokens, out) float32; ``adapter`` holds
    per-device ``a`` (D, in, r) and ``b`` (D, r, out), or is None."""
    y = frozen_matmul(x, w)
    if adapter is None:
        return y
    with jax.named_scope("lora"):
        xa = jnp.einsum("dti,dir->dtr", x.astype(jnp.float32), adapter["a"])
        return y + scale * jnp.einsum("dtr,dro->dto", xa, adapter["b"])
