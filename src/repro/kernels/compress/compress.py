"""Pallas TPU kernels: fused select/pack/EF for the comm uplink.

Three single-pass kernels cover the whole compressor zoo (semantics and
wire formats defined by ``ref.py`` — these must match it bit-for-bit in
interpret mode):

* **select** (top-k / rand-k): given the k-th-largest score and the
  number of ties it may keep as SMEM scalars, compute the keep set
  (strictly-above entries plus ``== threshold`` ties filled in
  flat-index order — ``lax.top_k``'s exact kept set, see
  ``ref._select``), each kept coordinate's global
  rank (its slot in the ``(k,)`` wire buffer), the dense decompressed
  value, and — in the EF variant — the error-feedback residual, in one
  VMEM-resident pass. The strict/tie prefix counts are cumulative
  sums done as MXU matmuls against triangular 0/1 matrices (lane-axis
  prefix via a (128,128) upper-triangle, row-axis prefix via a
  (rows,rows) strict lower-triangle) — no scatter, no sort, no
  unsupported scan.
* **ef-quantize-int8**: ``msg = delta + ef`` -> row absmax scale ->
  stochastic round -> packed int8 + scales + dq + ef_new. Subsumes the
  ``kernels/quantize`` forward (that kernel remains for the bare op).
* **sign**: sign bits packed 8-per-byte via one MXU matmul against a
  (128,16) group-indicator matrix (int32 in the kernel, narrowed to
  uint8 by the wrapper), plus ``dq = scale * sign`` and the
  EF residual. The global ``mean(|msg|)`` scale is computed by the XLA
  wrapper and passed in, keeping it bit-identical to the unfused path.

All kernels are gridless single blocks: the whole (rows, 128) array is
one VMEM block, so they vmap safely over the stacked (M, N) sender axes
(no program_id / scratch state for the batching rule to break). That
bounds leaf size to VMEM — ``PALLAS_MAX_ELEMS`` floats per leaf per
sender — and bigger leaves are routed to the XLA reference by
``ops.resolve_leaf_mode`` (DESIGN.md §10).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128

# VMEM ceiling for the gridless kernels: the largest flat leaf (in
# elements) that every kernel here compiles at for a TPU v5e. Measured
# with the chip's compiler on a described v5e topology: ef_randk (three
# inputs, three outputs, the (rows, rows) prefix triangle) is the first
# to run out of VMEM, at 1921 rows. tests/test_tpu_compile.py compiles
# every kernel at this bound. ``ops.resolve_leaf_mode`` sends bigger
# leaves to the XLA reference (same bits).
PALLAS_MAX_ELEMS = 1920 * LANES


def _pad_rows(x, size):
    rows = pl.cdiv(size, LANES)
    pad = rows * LANES - size
    if pad:
        x = jnp.pad(x, (0, pad))
    return x.reshape(rows, LANES), rows


def _select_core(score, v, s_ref, scale, size):
    """Shared select math, mirroring ``ref._select``: keep strictly-above
    entries unconditionally, fill the remaining ``cap`` slots with
    ``== thresh`` ties in flat-index order (``lax.top_k``'s exact kept
    set), global ranks via matmul prefix counts. ``s_ref`` is the SMEM
    (1, 2) pair (thresh, cap) from :func:`_select_scalars`: Mosaic
    splats an SMEM scalar to a vector, but cannot broadcast a (1, 1)
    vector across both sublanes and lanes."""
    thresh = s_ref[0, 0]
    cap = s_ref[0, 1]
    rows = score.shape[0]
    ridx = lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    lidx = lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    real = (ridx * LANES + lidx) < size
    strict = (score > thresh) & real
    tie = (score == thresh) & real
    li = lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    lj = lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    lane_tri = (li <= lj).astype(jnp.float32)
    ri = lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    rj = lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    row_tri = (rj < ri).astype(jnp.float32)

    def inc_count(mask):
        # Inclusive flat-order prefix count of ``mask``. HIGHEST
        # precision: the MXU's default f32 matmul is inexact above ~2^8
        # and these products must be exact integer counts.
        incl = jnp.dot(mask.astype(jnp.float32), lane_tri,
                       precision=lax.Precision.HIGHEST)
        prefix = jnp.dot(row_tri, incl[:, LANES - 1:LANES],
                         precision=lax.Precision.HIGHEST)
        return prefix + incl

    inc_s = inc_count(strict)
    inc_t = inc_count(tie)
    # counts are exact integers in f32 (< 2^24)
    sel = strict | (tie & (inc_t <= cap))
    rank = (inc_s + jnp.minimum(inc_t, cap)).astype(jnp.int32) - 1
    dq = jnp.where(sel, v * scale, jnp.zeros((), v.dtype))
    ranks = jnp.where(sel, rank, -1)
    return dq, ranks


def _topk_kernel(s_ref, v_ref, dq_ref, rk_ref, *, size):
    v = v_ref[...]
    dq, rk = _select_core(jnp.abs(v.astype(jnp.float32)), v, s_ref,
                          1.0, size)
    dq_ref[...] = dq
    rk_ref[...] = rk


def _ef_topk_kernel(s_ref, d_ref, e_ref, dq_ref, rk_ref, ef_ref, *, size):
    msg = d_ref[...] + e_ref[...]
    dq, rk = _select_core(jnp.abs(msg.astype(jnp.float32)), msg, s_ref,
                          1.0, size)
    dq_ref[...] = dq
    rk_ref[...] = rk
    ef_ref[...] = msg - dq


def _randk_kernel(s_ref, u_ref, v_ref, dq_ref, rk_ref, *, scale, size):
    dq, rk = _select_core(u_ref[...].astype(jnp.float32), v_ref[...],
                          s_ref, scale, size)
    dq_ref[...] = dq
    rk_ref[...] = rk


def _ef_randk_kernel(s_ref, u_ref, d_ref, e_ref, dq_ref, rk_ref, ef_ref,
                     *, size):
    msg = d_ref[...] + e_ref[...]
    dq, rk = _select_core(u_ref[...].astype(jnp.float32), msg, s_ref,
                          1.0, size)
    dq_ref[...] = dq
    rk_ref[...] = rk
    ef_ref[...] = msg - dq


def _ef_quant_kernel(d_ref, e_ref, n_ref, q_ref, s_ref, dq_ref, ef_ref):
    msg = d_ref[...] + e_ref[...]
    m = msg.astype(jnp.float32)
    u = n_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(m), axis=1, keepdims=True)
    scale = jnp.maximum(absmax * (1.0 / 127.0), 1e-12)
    q = jnp.clip(jnp.floor(m / scale + u), -127.0, 127.0)
    dq = (q * scale).astype(msg.dtype)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale
    dq_ref[...] = dq
    ef_ref[...] = msg - dq


def _pack_bits(v):
    """(rows,128) values -> (rows,16) int32 sign bytes via one MXU matmul:
    lane 8c+j contributes 2^j to byte c, matching ref._pack_bits. Mosaic
    has no f32 -> uint8 cast, so the wrapper narrows to uint8."""
    rows = v.shape[0]
    lidx = lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    w = jnp.exp2((lidx % 8).astype(jnp.float32))
    gl = lax.broadcasted_iota(jnp.int32, (LANES, LANES // 8), 0)
    gc = lax.broadcasted_iota(jnp.int32, (LANES, LANES // 8), 1)
    group = ((gl // 8) == gc).astype(jnp.float32)
    nonneg = (v >= 0).astype(jnp.float32)
    return jnp.dot(nonneg * w, group,
                   precision=lax.Precision.HIGHEST).astype(jnp.int32)


def _sign_kernel(s_ref, v_ref, b_ref, dq_ref):
    v = v_ref[...]
    b_ref[...] = _pack_bits(v)
    dq_ref[...] = (s_ref[0, 0] * jnp.sign(v.astype(jnp.float32))
                   ).astype(v.dtype)


def _ef_sign_kernel(s_ref, d_ref, e_ref, b_ref, dq_ref, ef_ref):
    msg = d_ref[...] + e_ref[...]
    b_ref[...] = _pack_bits(msg)
    dq = (s_ref[0, 0] * jnp.sign(msg.astype(jnp.float32))).astype(msg.dtype)
    dq_ref[...] = dq
    ef_ref[...] = msg - dq


def _call(kernel, outs, *ins, interpret, name, smem=None):
    """Gridless pallas_call named ``name``: every operand/output is one
    whole block. ``smem`` (a (1, n) f32 row of scalars) rides in SMEM as
    the kernel's first operand."""
    in_specs = [pl.BlockSpec(memory_space=pltpu.VMEM)] * len(ins)
    if smem is not None:
        in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + in_specs
        ins = (smem,) + ins
    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(s, d) for s, d in outs],
        in_specs=in_specs,
        interpret=interpret, name=name,
    )(*ins)


def _scalars(*xs):
    return jnp.stack([jnp.asarray(x, jnp.float32) for x in xs]).reshape(
        1, len(xs))


def _select_scalars(score, thresh, k):
    """SMEM (thresh, cap) for the select kernels: ``cap = k - n_strict``
    is the number of ``== thresh`` ties kept (an exact count in f32)."""
    n_strict = jnp.sum((score > thresh).astype(jnp.int32))
    return _scalars(thresh, k - n_strict)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_select_flat(v, thresh, *, k: int, interpret: bool = False):
    """Flat (p,) fused top-k select+rank. thresh is the k-th largest
    |v| (see ref.kth_threshold). Returns (dq (p,), ranks (p,) i32)."""
    (size,) = v.shape
    v2, rows = _pad_rows(v, size)
    dq, rk = _call(
        functools.partial(_topk_kernel, size=size),
        [((rows, LANES), v.dtype), ((rows, LANES), jnp.int32)],
        v2, interpret=interpret, name="topk_select_flat",
        smem=_select_scalars(jnp.abs(v.astype(jnp.float32)), thresh, k))
    return dq.reshape(-1)[:size], rk.reshape(-1)[:size]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def ef_topk_select_flat(delta, ef, thresh, *, k: int,
                        interpret: bool = False):
    """Fused EF + top-k on flat (p,) arrays. thresh is the k-th largest
    |delta + ef|. Returns (dq, ranks, ef_new)."""
    (size,) = delta.shape
    d2, rows = _pad_rows(delta, size)
    e2, _ = _pad_rows(ef, size)
    score = jnp.abs((delta + ef).astype(jnp.float32))
    dq, rk, en = _call(
        functools.partial(_ef_topk_kernel, size=size),
        [((rows, LANES), delta.dtype), ((rows, LANES), jnp.int32),
         ((rows, LANES), delta.dtype)],
        d2, e2, interpret=interpret, name="ef_topk_select_flat",
        smem=_select_scalars(score, thresh, k))
    return (dq.reshape(-1)[:size], rk.reshape(-1)[:size],
            en.reshape(-1)[:size])


@functools.partial(jax.jit, static_argnames=("k", "scale", "interpret"))
def randk_select_flat(u, v, thresh, *, k: int, scale: float,
                      interpret: bool = False):
    """Flat fused rand-k select+rank; thresh is the k-th largest uniform
    score u. Returns (dq, ranks)."""
    (size,) = v.shape
    u2, rows = _pad_rows(u, size)
    v2, _ = _pad_rows(v, size)
    dq, rk = _call(
        functools.partial(_randk_kernel, scale=scale, size=size),
        [((rows, LANES), v.dtype), ((rows, LANES), jnp.int32)],
        u2, v2, interpret=interpret, name="randk_select_flat",
        smem=_select_scalars(u.astype(jnp.float32), thresh, k))
    return dq.reshape(-1)[:size], rk.reshape(-1)[:size]


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def ef_randk_select_flat(u, delta, ef, thresh, *, k: int,
                         interpret: bool = False):
    """Fused EF + rand-k (contractive). Returns (dq, ranks, ef_new)."""
    (size,) = delta.shape
    u2, rows = _pad_rows(u, size)
    d2, _ = _pad_rows(delta, size)
    e2, _ = _pad_rows(ef, size)
    dq, rk, en = _call(
        functools.partial(_ef_randk_kernel, size=size),
        [((rows, LANES), delta.dtype), ((rows, LANES), jnp.int32),
         ((rows, LANES), delta.dtype)],
        u2, d2, e2, interpret=interpret, name="ef_randk_select_flat",
        smem=_select_scalars(u.astype(jnp.float32), thresh, k))
    return (dq.reshape(-1)[:size], rk.reshape(-1)[:size],
            en.reshape(-1)[:size])


@functools.partial(jax.jit, static_argnames=("interpret",))
def ef_quantize_int8_flat(delta, ef, noise, *, interpret: bool = False):
    """Fused EF + stochastic int8 quantize/pack on flat (p,) arrays.
    Returns (q (p,) i8, scales (rows,) f32, dq (p,), ef_new (p,))."""
    (size,) = delta.shape
    d2, rows = _pad_rows(delta, size)
    e2, _ = _pad_rows(ef, size)
    n2, _ = _pad_rows(noise, size)
    q, s, dq, en = _call(
        _ef_quant_kernel,
        [((rows, LANES), jnp.int8), ((rows, 1), jnp.float32),
         ((rows, LANES), delta.dtype), ((rows, LANES), delta.dtype)],
        d2, e2, n2, interpret=interpret, name="ef_quantize_int8_flat")
    return (q.reshape(-1)[:size], s.reshape(-1), dq.reshape(-1)[:size],
            en.reshape(-1)[:size])


@functools.partial(jax.jit, static_argnames=("interpret",))
def sign_compress_flat(v, scale, *, interpret: bool = False):
    """Flat fused sign+pack; ``scale`` (the global mean |v|) is computed
    by the caller. Returns (bits (rows,16) u8, dq (p,))."""
    (size,) = v.shape
    v2, rows = _pad_rows(v, size)
    bits, dq = _call(
        _sign_kernel,
        [((rows, LANES // 8), jnp.int32), ((rows, LANES), v.dtype)],
        v2, interpret=interpret, name="sign_compress_flat",
        smem=_scalars(scale))
    return bits.astype(jnp.uint8), dq.reshape(-1)[:size]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ef_sign_compress_flat(delta, ef, scale, *, interpret: bool = False):
    """Fused EF + sign+pack. Returns (bits, dq, ef_new)."""
    (size,) = delta.shape
    d2, rows = _pad_rows(delta, size)
    e2, _ = _pad_rows(ef, size)
    bits, dq, en = _call(
        _ef_sign_kernel,
        [((rows, LANES // 8), jnp.int32), ((rows, LANES), delta.dtype),
         ((rows, LANES), delta.dtype)],
        d2, e2, interpret=interpret, name="ef_sign_compress_flat",
        smem=_scalars(scale))
    return (bits.astype(jnp.uint8), dq.reshape(-1)[:size],
            en.reshape(-1)[:size])
