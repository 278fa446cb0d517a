"""Plain reference of DeepSeek-V2-Lite's share on one chip, fine-tuned
as LoRA adapters over a frozen base: straightforward jax.numpy in the
dtype the caller gives (float32 for the reference, the bfloat16 base
upcast), every product at the precision the caller gives (HIGHEST for
the reference). One device at a time: ``loss`` and ``apply`` take one
device's adapters and batch.

It follows the published equations (arXiv 2405.04434; the model card's
``modeling_deepseek.py``) with these departures, each the program's too:

* the chip's share: the router scores all 64 experts and takes the
  greedy top-6 of all of them, and only the experts this chip holds
  (``first_held_expert`` and the next ``n_routed_experts``) add their
  part; what the absent experts would add is left out;
* a frozen router and no auxiliary loss (``seq_aux`` is for
  pretraining);
* LoRA (rank 16, alpha 32) on q, kv_a, kv_b and o of every layer.

Independent of the program's code: rope rotates each interleaved pair
(2i, 2i+1) as a complex number and keeps the pairs in place (the
program de-interleaves them, as the model card does; q.k is the same),
attention is one explicit softmax over every (query, key) of a
sequence, and the expert sum loops over the held experts with masks,
every expert applied to every token. Layers and experts are loops
(``lax.scan``), so the program compiles each body once."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import custom_batching

TARGETS = ("wq", "wkva", "wkvb", "wo")


def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(dtype)


def _dims(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    return {"wq": (d, h * (nope + rope)), "wkva": (d, r + rope),
            "wkvb": (r, h * (nope + dv)), "wo": (h * dv, d)}


def _layer(key, cfg, moe, dtype):
    d = cfg["hidden_size"]
    dims = _dims(cfg)
    ks = iter(jax.random.split(key, 12))
    p = {"norm1": jnp.ones((d,), dtype), "norm2": jnp.ones((d,), dtype),
         "mla": {name: _normal(next(ks), dims[name], dims[name][0], dtype)
                 for name in TARGETS}}
    p["mla"]["kv_norm"] = jnp.ones((cfg["kv_lora_rank"],), dtype)
    if moe:
        n, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
        fs = f * cfg["n_shared_experts"]
        p["moe"] = {
            "router": _normal(next(ks), (d, cfg["router_experts"]),
                              d, dtype),
            "experts": {"w_gate": _normal(next(ks), (n, d, f), d, dtype),
                        "w_up": _normal(next(ks), (n, d, f), d, dtype),
                        "w_down": _normal(next(ks), (n, f, d), f, dtype)},
            "shared": {"w_gate": _normal(next(ks), (d, fs), d, dtype),
                       "w_up": _normal(next(ks), (d, fs), d, dtype),
                       "w_down": _normal(next(ks), (fs, d), fs, dtype)}}
    else:
        f = cfg["intermediate_size"]
        p["mlp"] = {"w_gate": _normal(next(ks), (d, f), d, dtype),
                    "w_up": _normal(next(ks), (d, f), d, dtype),
                    "w_down": _normal(next(ks), (f, d), f, dtype)}
    return p


def groups(cfg):
    k = cfg["first_k_dense_replace"]
    return [(g, c) for g, c in (("dense", k),
                                ("moe", cfg["num_hidden_layers"] - k)) if c]


def init(key, cfg, dtype=jnp.bfloat16):
    """(base, adapters) in the program's tree layout: the frozen base in
    ``dtype`` (layers stacked per group on a leading axis), and one
    device's adapters in float32, A normal of std 1/sqrt(in), B zero."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    kb, ka = jax.random.split(key)
    ks = jax.random.split(kb, 4)
    base = {"embed": jax.random.normal(ks[0], (v, d)).astype(dtype),
            "final_norm": jnp.ones((d,), dtype),
            "head": _normal(ks[1], (d, v), d, dtype)}
    adapters = {}
    dims, rank = _dims(cfg), cfg["lora"]["rank"]
    for i, (g, count) in enumerate(groups(cfg)):
        keys = jax.random.split(jax.random.fold_in(ks[2], i), count)
        layers = [_layer(k, cfg, g == "moe", dtype) for k in keys]
        base[g] = jax.tree.map(lambda *a: jnp.stack(a), *layers)
        adapters[g] = {}
        for j, t in enumerate(TARGETS):
            kk = jax.random.fold_in(jax.random.fold_in(ka, i), j)
            din, dout = dims[t]
            adapters[g][t] = {
                "a": _normal(kk, (count, din, rank), din, jnp.float32),
                "b": jnp.zeros((count, rank, dout), jnp.float32)}
    return base, adapters


def _mm(x, w, precision):
    return jnp.matmul(x, w.astype(x.dtype), precision=precision)


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _yarn_get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def inv_freq(cfg):
    """YaRN frequencies of the rope dimensions, as the model card sets
    them (``DeepseekV2YarnRotaryEmbedding``)."""
    rs, dim, base = (cfg["rope_scaling"], cfg["qk_rope_head_dim"],
                     cfg["rope_theta"])
    pos = np.arange(0, dim, 2) / dim
    extra, inter = 1.0 / base ** pos, 1.0 / (rs["factor"] * base ** pos)

    def corr_dim(rot):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    hi = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def _rope(x, cfg):
    """x (T, heads, rope): pair (2i, 2i+1) turned by t * freq_i."""
    rs = cfg["rope_scaling"]
    m = (_yarn_get_mscale(rs["factor"], rs["mscale"])
         / _yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]))
    t = x.shape[0]
    ang = np.arange(t)[:, None] * inv_freq(cfg)[None, :]
    rot = jnp.asarray(np.exp(1j * ang) * m, jnp.complex64)[:, None, :]
    xf = x.astype(jnp.float32)
    z = jax.lax.complex(xf[..., 0::2], xf[..., 1::2]) * rot
    return jnp.stack([z.real, z.imag], -1).reshape(x.shape).astype(x.dtype)


def _lora(x, w, ad, scale, precision):
    return _mm(x, w, precision) + scale * _mm(_mm(x, ad["a"], precision),
                                              ad["b"], precision)


def attention(p, ad, x, cfg, precision):
    """MLA of one sequence x (T, d) -> (T, d)."""
    h, nope, rope_d, dv, r = (cfg["num_attention_heads"],
                              cfg["qk_nope_head_dim"],
                              cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                              cfg["kv_lora_rank"])
    t = x.shape[0]
    scale = cfg["lora"]["alpha"] / cfg["lora"]["rank"]
    q = _lora(x, p["wq"], ad["wq"], scale, precision).reshape(
        t, h, nope + rope_d)
    kva = _lora(x, p["wkva"], ad["wkva"], scale, precision)
    c = _rms(kva[:, :r], p["kv_norm"], cfg["rms_norm_eps"])
    kv = _lora(c, p["wkvb"], ad["wkvb"], scale, precision).reshape(
        t, h, nope + dv)
    k_rope = _rope(kva[:, None, r:], cfg)                  # one for all heads
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cfg)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (t, h, rope_d))], -1)
    rs = cfg["rope_scaling"]
    m = _yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=precision) \
        * (nope + rope_d) ** -0.5 * m * m
    mask = np.tril(np.ones((t, t), bool))
    prob = jax.nn.softmax(jnp.where(mask, s.astype(jnp.float32), -jnp.inf),
                          -1).astype(x.dtype)
    o = jnp.einsum("hqk,khd->qhd", prob, kv[..., nope:],
                   precision=precision).reshape(t, h * dv)
    return _lora(o, p["wo"], ad["wo"], scale, precision)


def _swiglu(p, x, precision):
    return _mm(jax.nn.silu(_mm(x, p["w_gate"], precision))
               * _mm(x, p["w_up"], precision), p["w_down"], precision)


def moe(p, x, cfg, precision):
    """The held experts' part plus the shared experts: x (T, d)."""
    probs = jax.nn.softmax(_mm(x, p["router"], precision)
                           .astype(jnp.float32), -1)
    gate, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])

    def expert(y, e_ex):
        e, ex = e_ex
        mine = idx == cfg["first_held_expert"] + e
        g = jnp.sum(jnp.where(mine, gate, 0.0), -1)[:, None]
        return y + (g * _swiglu(ex, x, precision)).astype(x.dtype), None

    y, _ = jax.lax.scan(expert, _swiglu(p["shared"], x, precision),
                        (jnp.arange(cfg["n_routed_experts"]), p["experts"]))
    return y


def apply(base, adapters, tokens, cfg, precision, dtype=jnp.float32):
    """tokens (S, T) -> logits (S, T, vocab), one device's adapters."""
    b = jax.tree.map(lambda a: a.astype(dtype), base)
    ad = jax.tree.map(lambda a: a.astype(dtype), adapters)
    eps = cfg["rms_norm_eps"]

    def layer(g, h, p_pa):
        p, pa = p_pa
        h = h + jax.vmap(lambda s: attention(
            p["mla"], pa, s, cfg, precision))(_rms(h, p["norm1"], eps))
        x = _rms(h, p["norm2"], eps)
        if g == "moe":
            y = jax.vmap(lambda s: moe(p["moe"], s, cfg, precision))(x)
        else:
            y = _swiglu(p["mlp"], x, precision)
        return h + y, None

    h = b["embed"][tokens]
    for g, _ in groups(cfg):
        h, _ = jax.lax.scan(functools.partial(layer, g), h, (b[g], ad[g]))
    return _mm(_rms(h, b["final_norm"], eps), b["head"], precision) \
        .astype(jnp.float32)


def _loss(adapters, batch, base, cfg, precision, dtype):
    logits = apply(base, adapters, batch["x"], cfg, precision, dtype)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["y"][..., None], -1))


def device_loss(base, cfg, precision, dtype=jnp.float32):
    """``loss(adapters, batch)`` of one device, mean next-token
    cross-entropy, for ``chipbench.permfl_ref``. Under ``vmap`` (over
    teams, then devices) the loss and its gradient run one device at a
    time, so the reference fits beside the program's state."""
    base = jax.tree.map(lambda a: a.astype(dtype), base)   # once

    def plain(adapters, batch, base_):
        return _loss(adapters, batch, base_, cfg, precision, dtype)

    value = custom_batching.sequential_vmap(plain)
    value_and_grad = custom_batching.sequential_vmap(
        jax.value_and_grad(plain))

    @jax.custom_vjp
    def loss(adapters, batch, base_):
        return value(adapters, batch, base_)

    def fwd(adapters, batch, base_):
        return value_and_grad(adapters, batch, base_)

    def bwd(grad, ct):
        return (jax.tree.map(lambda g: (g * ct).astype(g.dtype), grad),
                None, None)

    loss.defvjp(fwd, bwd)
    return lambda adapters, batch: loss(adapters, batch, base)
