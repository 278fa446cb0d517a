"""Pure-jnp oracle for flash attention: chunked online-softmax GQA attention.

This is (a) the numerical oracle for the Pallas kernel and (b) the
implementation compiled on non-TPU backends (incl. the CPU dry-run) — it is
mathematically exact full attention, but blocked over the KV axis so the
peak temporary is O(q_chunk × kv_chunk) instead of O(seq²).

Supports: causal masking, sliding-window attention (window > 0), GQA
(num_q_heads a multiple of num_kv_heads), and an explicit kv_len for
decode (query positions offset to the end of the cache).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gqa_expand(k, n_q_heads):
    """(b, s, n_kv, d) -> (b, s, n_q, d) by repeating kv heads."""
    b, s, n_kv, d = k.shape
    if n_kv == n_q_heads:
        return k
    rep = n_q_heads // n_kv
    return jnp.repeat(k, rep, axis=2)


@functools.partial(jax.jit, static_argnames=("causal", "window", "kv_chunk"))
def attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                  q_offset=None, kv_chunk: int = 1024):
    """Blocked attention.

    q: (b, sq, hq, d); k, v: (b, skv, hkv, d). Returns (b, sq, hq, d).
    q_offset: scalar int (traced OK) — absolute position of q[0]
              (decode: cache_len). None means aligned-to-end.
    window: if > 0, attend only to keys within `window` positions back.
    """
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    k = _gqa_expand(k, hq)
    v = _gqa_expand(v, hq)
    orig_dtype = q.dtype
    qf = q.astype(jnp.float32) * (d ** -0.5)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    if q_offset is None:
        q_offset = skv - sq  # aligned-to-end convention
    q_pos = jnp.arange(sq) + q_offset           # (sq,)

    kv_chunk = min(kv_chunk, skv)
    n_chunks = (skv + kv_chunk - 1) // kv_chunk
    pad = n_chunks * kv_chunk - skv
    if pad:
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kf = kf.reshape(b, n_chunks, kv_chunk, hq, d)
    vf = vf.reshape(b, n_chunks, kv_chunk, hq, d)

    def body(carry, inp):
        m, l, acc = carry          # (b,hq,sq), (b,hq,sq), (b,hq,sq,d)
        kc, vc, cidx = inp         # (b,kv_chunk,hq,d) ×2, scalar
        kv_pos = cidx * kv_chunk + jnp.arange(kv_chunk)      # (kv_chunk,)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kc)            # (b,hq,sq,kc)
        mask = kv_pos[None, :] < skv                          # padding
        if causal:
            mask = mask & (kv_pos[None, :] <= q_pos[:, None])
        if window > 0:
            mask = mask & (kv_pos[None, :] > q_pos[:, None] - window)
        # fold the mask into s ONCE (an additive -inf bias): each extra
        # `where` over the (b,hq,sq,kc) score tensor is a full HBM pass at
        # dry-run scale — §Perf hillclimb 3. exp(NEG_INF-m) underflows to
        # exactly 0, so no second masking of p is needed once m >= 0
        # entries exist; fully-masked rows give l=0 and are guarded by the
        # final maximum(l, eps).
        s = s + jnp.where(mask[None, None], 0.0, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        scale = jnp.exp(jnp.minimum(m - m_new, 0.0))
        l_new = l * scale + p.sum(axis=-1)
        # p is consumed by an MXU matmul: store it in the activation dtype
        # (halves the dominant score-tensor read; the f32 row statistics
        # m/l keep the online softmax exact to bf16 rounding)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(orig_dtype),
                        vc.astype(orig_dtype)).astype(jnp.float32)
        acc_new = acc * scale[..., None] + pv
        return (m_new, l_new, acc_new), None

    from repro.sharding.constrain import constrain
    m0 = constrain(jnp.full((b, hq, sq), NEG_INF, jnp.float32),
                   "batch", "model", None)
    l0 = constrain(jnp.zeros((b, hq, sq), jnp.float32),
                   "batch", "model", None)
    acc0 = constrain(jnp.zeros((b, hq, sq, d), jnp.float32),
                     "batch", "model", None, None)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        (jnp.moveaxis(kf, 1, 0), jnp.moveaxis(vf, 1, 0), jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.moveaxis(out, 1, 2).astype(orig_dtype)  # (b,sq,hq,d)


def causal_attention_ref(q, k, v, *, scale: float):
    """The plain softmax that ``causal.causal_flash_attention`` fuses:
    q, k (B, H, T, dqk), v (B, H, T, dv) -> (B, H, T, dv) float32. q
    enters the product in k's dtype, the scores and softmax are float32,
    and the probabilities enter the product with v in v's dtype."""
    t = q.shape[-2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(k.dtype), k,
                   preferred_element_type=jnp.float32) * scale
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)
