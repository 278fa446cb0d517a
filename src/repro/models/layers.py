"""Shared neural-net building blocks (pure-pytree style, no framework).

Every "module" here is a pair of functions: ``*_init(key, ...) -> params``
and ``*_apply(params, x, ...) -> y``, with params as plain dicts of
jnp arrays. Model-parallel sharding is attached later by path-based
PartitionSpec rules (repro/sharding/specs.py), which is why leaf names are
stable and descriptive.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, d_in, d_out, dtype=jnp.float32, scale=None):
    scale = scale if scale is not None else (1.0 / jnp.sqrt(d_in))
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


def embed_init(key, vocab, d, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_apply(params, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm_apply(params, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def norm_init(cfg, d=None, dtype=jnp.float32):
    d = d or cfg.d_model
    return rmsnorm_init(d, dtype) if cfg.use_rmsnorm else layernorm_init(d, dtype)


def norm_apply(cfg, params, x):
    if cfg.use_rmsnorm:
        return rmsnorm_apply(params, x, cfg.norm_eps)
    return layernorm_apply(params, x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# rotary embeddings (RoPE + Qwen2-VL's M-RoPE)
# ---------------------------------------------------------------------------

def _rope_freqs(head_dim, theta):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta=10_000.0):
    """x: (b, s, h, d); positions: (b, s) int32 -> same shape."""
    d = x.shape[-1]
    freqs = _rope_freqs(d, theta)                       # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (b, s, d/2)
    sin = jnp.sin(angles)[:, :, None, :]
    cos = jnp.cos(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta=10_000.0, sections=(2, 1, 1)):
    """Qwen2-VL multimodal RoPE.

    x: (b, s, h, d); positions3: (b, s, 3) — (temporal, height, width)
    position ids. The d/2 frequency slots are split between the three
    components in ratio ``sections`` (Qwen2-VL uses 16/24/24 of 64; we use
    the same 1/4-3/8-3/8 proportions scaled to head_dim).
    """
    d = x.shape[-1]
    half = d // 2
    freqs = _rope_freqs(d, theta)                       # (half,)
    total = sum(sections)
    bounds = [half * sum(sections[:i + 1]) // total for i in range(3)]
    starts = [0, bounds[0], bounds[1]]
    comp = jnp.zeros(half, jnp.int32)
    comp = comp.at[starts[1]:bounds[1]].set(1)
    comp = comp.at[starts[2]:bounds[2]].set(2)
    # pick, per frequency slot, the position component it rotates with
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),                 # (b, s, 3)
        jnp.broadcast_to(comp[None, None, :],
                         positions3.shape[:2] + (half,)).astype(jnp.int32),
        axis=-1)                                        # (b, s, half)
    angles = pos * freqs
    sin = jnp.sin(angles)[:, :, None, :]
    cos = jnp.cos(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(max_len, d):
    """Whisper-style fixed sinusoidal embeddings: (max_len, d)."""
    pos = jnp.arange(max_len, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    angle = pos / (10_000.0 ** (dim / d))
    emb = jnp.zeros((max_len, d), jnp.float32)
    emb = emb.at[:, 0::2].set(jnp.sin(angle))
    emb = emb.at[:, 1::2].set(jnp.cos(angle))
    return emb


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu_init(key, d, d_ff, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": dense_init(k1, d, d_ff, dtype),
            "w_up": dense_init(k2, d, d_ff, dtype),
            "w_down": dense_init(k3, d_ff, d, dtype)}


def swiglu_apply(params, x):
    g = jax.nn.silu(x @ params["w_gate"])
    return (g * (x @ params["w_up"])) @ params["w_down"]


def frozen_matmul(x, w):
    """``x @ w`` for a frozen weight, which may be held in bfloat16: ``x``
    cast to ``w``'s dtype, the product accumulated and returned in
    float32."""
    return jnp.einsum("...i,io->...o", x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def frozen_swiglu(params, x):
    """``swiglu_apply`` of frozen weights, each product in float32."""
    g = jax.nn.silu(frozen_matmul(x, params["w_gate"]))
    return frozen_matmul(g * frozen_matmul(x, params["w_up"]),
                         params["w_down"])


def gelu_mlp_init(key, d, d_ff, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    return {"w_in": dense_init(k1, d, d_ff, dtype),
            "b_in": jnp.zeros((d_ff,), dtype),
            "w_out": dense_init(k2, d_ff, d, dtype),
            "b_out": jnp.zeros((d,), dtype)}


def gelu_mlp_apply(params, x):
    h = jax.nn.gelu(x @ params["w_in"] + params["b_in"])
    return h @ params["w_out"] + params["b_out"]
