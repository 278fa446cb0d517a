"""A decoder LM fine-tuned as per-device LoRA adapters over a frozen
base: the federated path's model for zoo architectures (``ModelSpec``
kind "zoo"; DeepSeek-V2 family).

    h = embed[tokens]
    each layer:  h += MLA(RMSNorm(h));  h += FFN(RMSNorm(h))
                 FFN: a dense SwiGLU in the first ``first_k_dense_replace``
                 layers, the held-experts MoE (``moe.held_apply``) after
    logits = RMSNorm(h) @ head             (untied)

Trees. ``base`` (frozen, bfloat16 on the chip): ``embed`` (V, d),
``dense`` and ``moe`` (each layer's weights stacked on a leading layer
axis), ``final_norm``, ``head`` (d, V). Adapters (float32):
``{"dense"|"moe": {target: {"a", "b"}}}`` with the same layer axis, one
adapter per target projection of ``cfg.lora``.

The federated functions take the adapters of every device stacked
(M, N, ...) and the batches {"x": tokens, "y": next tokens} (M, N, S, T),
and return (M, N) values: the frozen base's matmuls and the expert layer
see every device's tokens at once, and each device applies its own
adapters. Each layer is recomputed in the backward pass, and the head
and loss run a few devices at a time, so activations and logits fit
beside the federation's state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import layers, mla, moe
from repro.models.lora import lora_init

HEAD_CHUNK = 2      # devices whose logits exist at once


def _layer_init(key, cfg, is_moe, dtype):
    k1, k2 = jax.random.split(key)
    d = cfg.d_model
    p = {"norm1": jnp.ones((d,), dtype), "norm2": jnp.ones((d,), dtype),
         "mla": mla.mla_init(k1, cfg, dtype)}
    if is_moe:
        p["moe"] = moe.held_init(k2, cfg, dtype)
    else:
        p["mlp"] = layers.swiglu_init(k2, d, cfg.d_ff, dtype)
    return p


def _groups(cfg):
    """(name, layer count, is_moe) of the two layer groups."""
    k = cfg.first_k_dense_replace
    return [(g, c, g == "moe") for g, c in (("dense", k),
                                            ("moe", cfg.num_layers - k))
            if c]


def init_base(key, cfg, dtype=jnp.bfloat16):
    """The frozen base: normal weights of std 1/sqrt(fan in), unit norms."""
    ke, kh, kl = jax.random.split(key, 3)
    d, v = cfg.d_model, cfg.vocab_size
    base = {"embed": jax.random.normal(ke, (v, d)).astype(dtype),
            "final_norm": jnp.ones((d,), dtype),
            "head": layers.dense_init(kh, d, v, dtype)}
    for i, (g, count, is_moe) in enumerate(_groups(cfg)):
        keys = jax.random.split(jax.random.fold_in(kl, i), count)
        base[g] = jax.vmap(lambda k: _layer_init(k, cfg, is_moe, dtype))(keys)
    return base


def target_shapes(cfg) -> dict:
    """(in, out) of each projection an adapter may target."""
    a, d, h = cfg.mla, cfg.d_model, cfg.num_heads
    return {"wq": (d, h * (a.qk_nope_head_dim + a.qk_rope_head_dim)),
            "wkva": (d, a.kv_lora_rank + a.qk_rope_head_dim),
            "wkvb": (a.kv_lora_rank, h * (a.qk_nope_head_dim
                                          + a.v_head_dim)),
            "wo": (h * a.v_head_dim, d)}


def init_adapters(key, cfg):
    """One device's adapters: A normal of std 1/sqrt(in), B zero."""
    shapes = target_shapes(cfg)
    out = {}
    for i, (g, count, _) in enumerate(_groups(cfg)):
        kg = jax.random.fold_in(key, i)
        out[g] = {t: jax.vmap(lambda k, t=t: lora_init(
            k, *shapes[t], cfg.lora.rank))(
                jax.random.split(jax.random.fold_in(kg, j), count))
            for j, t in enumerate(cfg.lora.targets)}
    return out


def _block(cfg, is_moe, seq_len, h, p, ad):
    """One pre-norm residual block over (D, R, d) hidden states."""
    h = h + mla.mla_apply(
        p["mla"], cfg, layers.rmsnorm_apply({"scale": p["norm1"]}, h,
                                            cfg.norm_eps),
        seq_len=seq_len, adapters=ad, scale=cfg.lora.scale)
    x = layers.rmsnorm_apply({"scale": p["norm2"]}, h, cfg.norm_eps)
    nd, r, d = x.shape
    if is_moe:
        y, counters = moe.held_apply(p["moe"], cfg, x.reshape(nd * r, d))
        return h + y.reshape(nd, r, d), counters
    return h + layers.frozen_swiglu(p["mlp"], x), {}


def hidden(base, adapters, cfg, tokens):
    """tokens (D, S, T) with ``adapters`` stacked (D, ...), or None for
    the base alone -> (the final hidden states (D, S*T, d) f32,
    per-MoE-layer counters (layers,))."""
    nd, ns, t = tokens.shape
    h = base["embed"][tokens.reshape(nd, ns * t)].astype(jnp.float32)
    counters = {}
    for g, _, is_moe in _groups(cfg):
        # (D, layers, ...) adapters -> (layers, D, ...) to scan with the base
        ad = None if adapters is None else jax.tree.map(
            lambda a: jnp.moveaxis(a, 1, 0), adapters[g])
        step = jax.checkpoint(functools.partial(_block, cfg, is_moe, t))
        h, cs = jax.lax.scan(lambda c, pa: step(c, *pa), h, (base[g], ad))
        counters.update(cs)
    return h, counters


def _flat(tree, lead: int):
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[lead:]), tree)


def _head_logits(base, cfg, h):
    z = layers.rmsnorm_apply({"scale": base["final_norm"]}, h, cfg.norm_eps)
    return layers.frozen_matmul(z, base["head"])


def logits(base, adapters, cfg, tokens):
    """(D, S, T) tokens, adapters stacked (D, ...) or None -> (D, S, T,
    V) f32."""
    h, _ = hidden(base, adapters, cfg, tokens)
    return _head_logits(base, cfg, h).reshape(tokens.shape + (-1,))


def _per_device(base, adapters, batch, cfg, reduce):
    """reduce(logits (R, V), targets (R,)) per device -> (M, N)."""
    lead = batch["x"].shape[:2]
    h, _ = hidden(base, _flat(adapters, 2), cfg, _flat(batch["x"], 2))
    y = _flat(batch["y"], 2).reshape(h.shape[:2])

    @jax.checkpoint
    def one(hy):
        with jax.named_scope("head"):
            return reduce(_head_logits(base, cfg, hy[0]), hy[1])

    out = jax.lax.map(one, (h, y), batch_size=min(HEAD_CHUNK, h.shape[0]))
    return out.reshape(lead)


def _cross_entropy(z, y):
    lse = jax.nn.logsumexp(z, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(z, y[:, None], -1)[:, 0])


def _accuracy(z, y):
    return jnp.mean((jnp.argmax(z, axis=-1) == y).astype(jnp.float32))


@functools.lru_cache(maxsize=16)
def fns(cfg):
    """(loss, metric) of the federated path for ``cfg``: each
    ``f(adapters (M, N, ...), batch (M, N, ...), base) -> (M, N)``, the
    mean next-token cross-entropy and next-token accuracy of each device
    under its own adapters. One pair per configuration, so compiled
    rounds are shared across calls."""
    def loss(adapters, batch, base):
        return _per_device(base, adapters, batch, cfg, _cross_entropy)

    def metric(adapters, batch, base):
        return _per_device(base, adapters, batch, cfg, _accuracy)

    return loss, metric


def route_counters(base, adapters, cfg, batch):
    """Each MoE layer's counters for one step's tokens: every device's
    batch under its own adapters; {name: (layers,)}."""
    _, c = hidden(base, _flat(adapters, 2), cfg, _flat(batch["x"], 2))
    return c
