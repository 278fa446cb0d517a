"""Pallas TPU flash attention (causal / sliding-window, GQA).

Grid: (batch, q_heads, q_blocks, kv_blocks) — kv_blocks is the innermost,
sequential dimension; the online-softmax running state (m, l, acc) lives in
VMEM scratch and is carried across kv blocks. Block shapes are MXU-aligned:
(block_q, head_dim) q tiles against (block_kv, head_dim) kv tiles, with the
lane dimension a multiple of 128 for the systolic array.

GQA is handled in the BlockSpec index maps: the kv block loaded for q-head h
is kv-head ``h // (hq // hkv)`` — no materialized head repetition, so HBM
traffic for K/V is 1/group of the MHA equivalent.

``q_offset`` (the absolute position of q[0] — the cache length during
decode) is a *traced* scalar, delivered to the kernel via scalar prefetch
(SMEM) so a single compiled decode step serves every position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                 *, causal: bool, window: int, sm_scale: float, block_q: int,
                 block_kv: int, kv_len: int):
    kv_idx = pl.program_id(3)
    n_kv = pl.num_programs(3)
    q_offset = off_ref[0]

    @pl.when(kv_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_blk = pl.program_id(2)
    q_pos = q_offset + q_blk * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    k_pos = kv_idx * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)

    def _compute():
        q = q_ref[0, 0, :, :].astype(jnp.float32) * sm_scale   # (bq, d)
        k = k_ref[0, 0, :, :].astype(jnp.float32)              # (bkv, d)
        v = v_ref[0, 0, :, :].astype(jnp.float32)
        # zero out-of-range kv rows: beyond-kv_len blocks hold garbage and
        # 0 * garbage in the PV matmul would poison the accumulator.
        kv_valid = (kv_idx * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_kv, 1), 0)) < kv_len
        v = jnp.where(kv_valid, v, 0.0)
        k = jnp.where(kv_valid, k, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = k_pos < kv_len
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        # explicit mask on p: for fully-masked rows exp(NEG_INF - NEG_INF)
        # would be 1, not 0.
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal or window > 0:
        # Skip kv blocks fully masked by the causal/sliding-window structure
        # (this is where flash beats naive: ~2x for causal, seq/window for
        # SWA). Works with a traced q_offset because pl.when takes a traced
        # predicate.
        blk_min_q = q_offset + q_blk * block_q
        blk_max_q = blk_min_q + block_q - 1
        blk_min_k = kv_idx * block_kv
        blk_max_k = blk_min_k + block_kv - 1
        live = blk_min_k <= jnp.minimum(blk_max_q, kv_len - 1)
        if window > 0:
            live &= blk_max_k > blk_min_q - window
        pl.when(live)(_compute)
    else:
        _compute()

    @pl.when(kv_idx == n_kv - 1)
    def _finish():
        o_ref[0, 0, :, :] = (acc_scr[...] /
                             jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_kv", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset=None, block_q: int = 512,
                    block_kv: int = 512, interpret: bool = False):
    """q: (b, sq, hq, d); k, v: (b, skv, hkv, d) -> (b, sq, hq, d).

    q_offset: None (aligned-to-end) or a scalar (traced OK).
    """
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    if q_offset is None:
        q_offset = skv - sq
    q_offset = jnp.asarray(q_offset, jnp.int32).reshape(1)
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    grid = (b, hq, pl.cdiv(sq, block_q), pl.cdiv(skv, block_kv))
    group = max(hq // hkv, 1)

    qs = jnp.moveaxis(q, 2, 1)  # (b, hq, sq, d)
    ks = jnp.moveaxis(k, 2, 1)
    vs = jnp.moveaxis(v, 2, 1)

    kernel = functools.partial(
        _attn_kernel, causal=causal, window=window, sm_scale=d ** -0.5,
        block_q=block_q, block_kv=block_kv, kv_len=skv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h, i, j, off: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h, i, j, off: (b_, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h, i, j, off: (b_, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h, i, j, off: (b_, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        interpret=interpret, name="flash_attention",
    )(q_offset, qs, ks, vs)
    return jnp.moveaxis(out, 1, 2)
