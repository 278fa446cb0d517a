"""Causal attention for training, with its backward pass: the Pallas
splash-attention kernels that ship with JAX (forward, dq and dk/dv),
over a causal mask whose fully masked blocks are skipped, their DMA
included.

q and k may be wider than v (latent attention's 192 against 128), and
the softmax scale is explicit: q is multiplied by it in float32 before
its cast to k's dtype. Scores, softmax and the log-sum-exp stay float32
in VMEM; the forward pass keeps only o and the log-sum-exp for the
backward pass, so no (heads, T, T) matrix reaches HBM.

The kernel is built once per (heads, padded length, interpret) and
vmapped over the batch. Its blocks are multiples of 128, so a length
that is not one is padded: the padded keys lie after every real query,
and the causal mask keeps them out of every real row, so the result is
exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

LANES = 128
BLOCKS = (512, 256, 128)    # the largest that divides the length is used


@functools.lru_cache(maxsize=None)
def _kernel(heads: int, t: int, interpret: bool):
    b = next(c for c in BLOCKS if t % c == 0)
    sizes = splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        block_q_dq=b, block_kv_dq=b)
    mask = splash.MultiHeadMask([splash.CausalMask((t, t))] * heads)
    # the mask's block tables are arrays: make them concrete even when
    # the first call comes inside a trace, since the kernel is cached
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(mask, block_sizes=sizes, head_shards=1,
                                      q_seq_shards=1, interpret=interpret)


def causal_flash_attention(q, k, v, *, scale: float,
                           interpret: bool = False):
    """q, k (B, H, T, dqk), v (B, H, T, dv) -> (B, H, T, dv) float32:
    ``softmax(q k^T * scale + causal mask) v`` of each sequence and
    head, differentiable in q, k and v."""
    _, h, t, _ = q.shape
    q = (q.astype(jnp.float32) * scale).astype(k.dtype)
    pad = -t % LANES
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for a in (q, k, v))
    o = jax.vmap(_kernel(h, t + pad, interpret))(q, k, v)
    return o[:, :, :t].astype(jnp.float32)
