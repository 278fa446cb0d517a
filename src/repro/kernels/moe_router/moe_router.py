"""Pallas TPU kernel: fused MoE routing (softmax + top-k + renormalize).

One pass over the (block_tokens, experts) logits tile in VMEM produces the
top-k gate values and expert ids plus the per-expert load statistics that
feed the load-balance loss — XLA would otherwise materialize the full
softmax, run k sort passes, and re-read probs for the statistics.

top-k is computed by k iterations of (max, mask) — experts <= 64 here, so
each iteration is one VPU reduction over the lane dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _router_kernel(logits_ref, gates_ref, idx_ref, stats_ref, *,
                   top_k: int, renormalize: bool, num_tokens: int,
                   block_tokens: int):
    blk = pl.program_id(0)
    x = logits_ref[...].astype(jnp.float32)           # (bt, E)
    bt, e = x.shape
    row = blk * block_tokens + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
    valid = row < num_tokens                           # (bt, 1)

    m = x.max(axis=-1, keepdims=True)
    p = jnp.exp(x - m)
    p = p / p.sum(axis=-1, keepdims=True)              # softmax (bt, E)

    work = p
    gsum = jnp.zeros((bt, 1), jnp.float32)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bt, e), 1)
    sel_mask = jnp.zeros((bt, e), jnp.float32)         # k-hot selection
    for j in range(top_k):
        g = work.max(axis=-1, keepdims=True)           # (bt, 1)
        amax = jnp.argmax(work, axis=-1)               # (bt,)
        hot = cols == amax[:, None]
        work = jnp.where(hot, NEG_INF, work)
        sel_mask = sel_mask + hot.astype(jnp.float32)
        gates_ref[:, j] = g[:, 0].astype(gates_ref.dtype)
        idx_ref[:, j] = amax.astype(jnp.int32)
        gsum = gsum + g
    if renormalize:
        gates_ref[...] = (gates_ref[...].astype(jnp.float32) /
                          jnp.maximum(gsum, 1e-20)).astype(gates_ref.dtype)
    # per-expert stats for this block: sum of probs, count of selections
    pv = jnp.where(valid, p, 0.0)
    sv = jnp.where(valid, sel_mask, 0.0)
    stats_ref[0, 0, :] = pv.sum(axis=0)
    stats_ref[0, 1, :] = sv.sum(axis=0)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "renormalize", "block_tokens", "interpret"))
def route(logits, *, top_k: int, renormalize: bool = True,
          block_tokens: int = 1024, interpret: bool = False):
    """logits: (tokens, experts). Returns (gates, idx, aux) like ref
    (without the full probs tensor — the kernel's point is not to emit it).
    """
    t, e = logits.shape
    block_tokens = min(block_tokens, t)
    n_blocks = pl.cdiv(t, block_tokens)
    kernel = functools.partial(
        _router_kernel, top_k=top_k, renormalize=renormalize,
        num_tokens=t, block_tokens=block_tokens)
    gates, idx, stats = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((block_tokens, e), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_tokens, top_k), lambda i: (i, 0)),
            pl.BlockSpec((block_tokens, top_k), lambda i: (i, 0)),
            pl.BlockSpec((1, 2, e), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks * block_tokens, top_k), logits.dtype),
            jax.ShapeDtypeStruct((n_blocks * block_tokens, top_k), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, 2, e), jnp.float32),
        ],
        interpret=interpret, name="moe_route",
    )(logits)
    gates, idx = gates[:t], idx[:t]
    aux = {
        "mean_prob": stats[:, 0, :].sum(0) / t,
        "frac_tokens": stats[:, 1, :].sum(0) / (t * top_k),
    }
    return gates, idx, aux
