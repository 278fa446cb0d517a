"""Multi-head latent attention (DeepSeek-V2, arXiv 2405.04434 §2.1)
without query compression, with YaRN rope (arXiv 2309.00071).

For a token's hidden state h, with H heads:

    q                 = W_q h                  H x (nope + rope) values
    [c_kv, k_rope]    = W_kva h                kv_lora_rank + rope values
    c_kv              = RMSNorm(c_kv)
    [k_nope, v]       = W_kvb c_kv             per head nope + v values
    q_rope, k_rope    = YaRN rope; k_rope is one key shared by all heads
    k                 = [k_nope; k_rope],  q = [q_nope; q_rope]
    out               = W_o concat_h(causal softmax(q.k * s) v)

with ``s = (nope + rope)^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim *
ln(factor) + 1``; rope's cos and sin are scaled by m_mscale / m_all
(1 for DeepSeek-V2). Rope pairs the dimensions as the published
modeling code does: dimensions (2i, 2i+1) rotate by frequency i and come
out de-interleaved, [evens; odds].

Every projection may take a LoRA adapter (``models.lora``). q, k and
v are laid out heads-major, (sequences, H, T, d), for the attention
core, ``kernels.flash_attention.causal_attention``: one fused causal
kernel with its own backward pass, so no (heads, T, T) score matrix
reaches HBM.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import causal_attention
from repro.models import layers
from repro.models.lora import project


def mla_init(key, cfg, dtype=jnp.float32):
    a, d, h = cfg.mla, cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": layers.dense_init(ks[0], d, h * (a.qk_nope_head_dim
                                               + a.qk_rope_head_dim), dtype),
        "wkva": layers.dense_init(ks[1], d, a.kv_lora_rank
                                  + a.qk_rope_head_dim, dtype),
        "kv_norm": jnp.ones((a.kv_lora_rank,), dtype),
        "wkvb": layers.dense_init(ks[2], a.kv_lora_rank,
                                  h * (a.qk_nope_head_dim + a.v_head_dim),
                                  dtype),
        "wo": layers.dense_init(ks[3], h * a.v_head_dim, d, dtype),
    }


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
    """(dim/2,) rope frequencies: interpolated by ``factor`` below the
    correction range, kept above it, ramped linearly between."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if yarn is None:
        return extra.astype(np.float32)
    inter = extra / yarn.factor

    def corr(rot):
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (rot * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def softmax_scale(cfg) -> float:
    a = cfg.mla
    s = (a.qk_nope_head_dim + a.qk_rope_head_dim) ** -0.5
    if cfg.yarn is not None and cfg.yarn.mscale_all_dim:
        m = yarn_mscale(cfg.yarn.factor, cfg.yarn.mscale_all_dim)
        s *= m * m
    return s


def rope_cos_sin(cfg, seq_len: int):
    """(T, rope/2) cos and sin, scaled by m_mscale / m_all."""
    y = cfg.yarn
    freq = yarn_inv_freq(cfg.mla.qk_rope_head_dim, cfg.rope_theta, y)
    ang = np.arange(seq_len, dtype=np.float32)[:, None] * freq[None]
    m = 1.0 if y is None else (yarn_mscale(y.factor, y.mscale)
                               / yarn_mscale(y.factor, y.mscale_all_dim))
    return jnp.asarray(np.cos(ang) * m), jnp.asarray(np.sin(ang) * m)


def rope(x, cos, sin):
    """x: (..., T, rope) -> de-interleaved and rotated."""
    x = x.astype(jnp.float32)
    ev, od = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([ev * cos - od * sin, od * cos + ev * sin],
                           axis=-1)


def mla_apply(p, cfg, x, *, seq_len: int, adapters=None, scale=1.0):
    """x: (D, S*T, d) f32 hidden states of S sequences of ``seq_len``
    tokens per device -> (D, S*T, d) f32. ``adapters``: per-device LoRA
    adapters keyed by projection name, or None."""
    a, h = cfg.mla, cfg.num_heads
    dn, dr, dv = a.qk_nope_head_dim, a.qk_rope_head_dim, a.v_head_dim
    nd, r, _ = x.shape
    ns = r // seq_len
    ad = adapters or {}
    act = p["wq"].dtype

    def proj(name, inp):
        return project(inp, p[name], ad.get(name), scale)

    def heads(y, d):
        """(D, S*T, H*d) -> (D*S, H, T, d)."""
        return y.reshape(nd * ns, seq_len, -1, d).swapaxes(1, 2)

    with jax.named_scope("mla"):
        q = heads(proj("wq", x), dn + dr)
        kva = proj("wkva", x)
        c_kv = layers.rmsnorm_apply({"scale": p["kv_norm"]},
                                    kva[..., :a.kv_lora_rank], cfg.norm_eps)
        kv = heads(proj("wkvb", c_kv), dn + dv)
        k_rope = heads(kva[..., a.kv_lora_rank:], dr)
        cos, sin = rope_cos_sin(cfg, seq_len)
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], cos, sin)], -1)
        k_rope = jnp.broadcast_to(rope(k_rope, cos, sin),
                                  (nd * ns, h, seq_len, dr))
        k = jnp.concatenate([kv[..., :dn], k_rope], -1)
        o = causal_attention(q, k.astype(act), kv[..., dn:].astype(act),
                             scale=softmax_scale(cfg))
        return proj("wo", o.swapaxes(1, 2).reshape(nd, r, h * dv))
