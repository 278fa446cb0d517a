"""Fine-grained Mixture-of-Experts layer (DeepSeek-MoE / DBRX style).

Shared experts (always on) + routed experts with top-k gating and
capacity-based dispatch. Routing is the fused kernel
(repro.kernels.moe_router); dispatch/combine are one-hot einsums over token
*groups* (GShard style) so the dispatch tensor is
(groups, group_size, E, capacity) with a bounded group_size — the
expert matmuls are plain batched einsums the MXU loves, and the experts
dimension is what the `model`/expert-parallel mesh axis shards.

``held_apply`` is a chip's share of a layer instead: told which experts
it holds, it routes over all of them, drops no pair, and runs the pairs
that land on its experts as grouped matmuls (DeepSeek-V2-Lite's
federated LoRA path, ``models.lora_lm``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.moe_router import route_topk
from repro.models import layers

DEFAULT_GROUP = 1024


def moe_init(key, cfg, dtype=jnp.float32):
    d = cfg.d_model
    m = cfg.moe
    e_ff = m.expert_d_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    scale = 1.0 / jnp.sqrt(d)

    def expert_bank(k_, n):
        k1, k2, k3 = jax.random.split(k_, 3)
        return {
            "w_gate": (jax.random.normal(k1, (n, d, e_ff)) * scale).astype(dtype),
            "w_up": (jax.random.normal(k2, (n, d, e_ff)) * scale).astype(dtype),
            "w_down": (jax.random.normal(k3, (n, e_ff, d)) *
                       (1.0 / jnp.sqrt(e_ff))).astype(dtype),
        }

    p = {
        "router": layers.dense_init(ks[0], d, m.num_experts, jnp.float32),
        "experts": expert_bank(ks[1], m.num_experts),
    }
    if m.num_shared_experts:
        p["shared"] = layers.swiglu_init(ks[2], d, e_ff * m.num_shared_experts,
                                         dtype)
    return p


def _capacity(group_size: int, num_experts: int, top_k: int,
              factor: float) -> int:
    cap = int(group_size * top_k / num_experts * factor)
    return max(cap, top_k)


@functools.partial(jax.jit, static_argnames=("cfg", "group_size"))
def moe_apply(params, cfg, x, *, group_size: int = DEFAULT_GROUP):
    """x: (b, s, d) -> (y: (b, s, d), aux_loss: scalar).

    Tokens over capacity are dropped (their contribution is the shared
    experts + residual only) — standard capacity-based MoE semantics.
    """
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)

    gs = min(group_size, t)
    n_groups = -(-t // gs)
    pad = n_groups * gs - t
    xp = jnp.pad(xt, ((0, pad), (0, 0))) if pad else xt

    logits = (xp.astype(jnp.float32) @ params["router"])      # (T, E)
    gates, idx, aux = route_topk(logits, top_k=m.top_k)       # (T,k) ×2
    # drop gates of padded tokens so they don't consume capacity weights
    if pad:
        valid = jnp.arange(n_groups * gs) < t
        gates = jnp.where(valid[:, None], gates, 0.0)

    e = m.num_experts
    cap = _capacity(gs, e, m.top_k, m.capacity_factor)
    gates_g = gates.reshape(n_groups, gs, m.top_k)
    idx_g = idx.reshape(n_groups, gs, m.top_k)

    # position of each (token, choice) within its expert's capacity buffer
    sel = jax.nn.one_hot(idx_g, e, dtype=jnp.float32)         # (g, gs, k, E)
    # priority: earlier tokens (and earlier choices) win capacity
    sel_flat = sel.reshape(n_groups, gs * m.top_k, e)
    pos_in_expert = jnp.cumsum(sel_flat, axis=1) - sel_flat    # (g, gs*k, E)
    pos_in_expert = pos_in_expert.reshape(n_groups, gs, m.top_k, e)
    within_cap = pos_in_expert < cap
    sel = sel * within_cap

    pos_idx = (pos_in_expert * sel).sum(-1).astype(jnp.int32)  # (g, gs, k)
    cap_onehot = jax.nn.one_hot(pos_idx, cap, dtype=jnp.float32)  # (g,gs,k,C)
    # dispatch: (g, gs, E, C)
    dispatch = jnp.einsum("gske,gskc->gsec", sel, cap_onehot)
    combine = jnp.einsum("gske,gskc,gsk->gsec", sel, cap_onehot,
                         gates_g.astype(jnp.float32))

    from repro.sharding.constrain import constrain
    xg = xp.reshape(n_groups, gs, d)
    dispatch = constrain(dispatch.astype(x.dtype),
                         "batch", None, "model", None)
    combine = constrain(combine, "batch", None, "model", None)
    expert_in = constrain(jnp.einsum("gsec,gsd->gecd", dispatch, xg),
                          "batch", "model", None, None)
    w_g, w_u, w_d = (params["experts"][k_] for k_ in ("w_gate", "w_up",
                                                      "w_down"))
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in, w_g))
    h = h * jnp.einsum("gecd,edf->gecf", expert_in, w_u)
    h = constrain(h, "batch", "model", None, None)
    expert_out = constrain(jnp.einsum("gecf,efd->gecd", h, w_d),
                           "batch", "model", None, None)
    yt = jnp.einsum("gsec,gecd->gsd", combine.astype(x.dtype), expert_out)
    yt = yt.reshape(n_groups * gs, d)[:t]

    if m.num_shared_experts:
        yt = yt + layers.swiglu_apply(params["shared"], xt)

    aux_loss = m.router_aux_weight * e * jnp.sum(
        aux["frac_tokens"] * aux["mean_prob"])
    return yt.reshape(b, s, d), aux_loss


# ---------------------------------------------------------------------------
# the held-experts layer: a chip's share of the routed experts, no drops
# ---------------------------------------------------------------------------

def held_init(key, cfg, dtype=jnp.float32):
    """Router over all ``num_experts``; the banks of the experts this
    chip holds (``cfg.moe.held``); the shared experts as one SwiGLU."""
    m, d = cfg.moe, cfg.d_model
    ks = jax.random.split(key, 5)
    n = m.held_range[1]
    f = m.expert_d_ff

    def bank(k, shape, fan_in):
        return (jax.random.normal(k, shape) / jnp.sqrt(fan_in)).astype(dtype)

    p = {"router": layers.dense_init(ks[0], d, m.num_experts, dtype),
         "experts": {"w_gate": bank(ks[1], (n, d, f), d),
                     "w_up": bank(ks[2], (n, d, f), d),
                     "w_down": bank(ks[3], (n, f, d), f)}}
    if m.num_shared_experts:
        p["shared"] = layers.swiglu_init(ks[4], d, f * m.num_shared_experts,
                                         dtype)
    return p


def route_held(logits, cfg):
    """Softmax scoring and greedy top-k over every expert, then the
    pairs (token, choice) that land on held experts, sorted by expert.

    Returns ``(order, pair_gate, sizes)``: ``order`` (T*k,) pair ids
    sorted by held expert (pairs on absent experts last), each pair's
    gate, and the (held,) number of pairs per held expert. The expert
    choice is not differentiated; the gates are."""
    m = cfg.moe
    first, n = m.held_range
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, idx, _ = route_topk(jax.lax.stop_gradient(logits), top_k=m.top_k,
                           renormalize=False)
    # DeepSeek-V2's published gating: norm_topk_prob false and
    # routed_scaling_factor 1, so the softmax scores are the gates as they are
    gates = jnp.take_along_axis(probs, idx, axis=-1)
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < n), local, n)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=n + 1)[:n].astype(jnp.int32)
    return order, gates.reshape(-1), sizes


def held_apply(p, cfg, x):
    """x: (T, d) -> (y (T, d) f32, counters).

    ``y = sum over the token's top-k choices e that this chip holds of
    s_e SwiGLU_e(x) + SwiGLU_shared(x)``. Every pair routed to a held
    expert is computed: the sorted pairs go through the expert banks as
    grouped matmuls (``lax.ragged_dot``, whose work follows the group
    sizes) in ``top_k`` chunks of T rows, and a chunk past the last held
    pair is skipped, so the work follows the pairs routed here.
    ``counters``: ``held_pairs``, ``dropped_pairs`` (pairs on held
    experts that no chunk covered: bookkeeping, 0 by construction, since
    the layer has no capacity), ``max_per_expert``, ``mean_per_expert``.
    """
    m = cfg.moe
    t, d = x.shape
    n = m.held_range[1]
    bank = p["experts"]
    act = bank["w_gate"].dtype
    with jax.named_scope("moe.route"):
        logits = jnp.einsum("td,de->te", x.astype(p["router"].dtype),
                            p["router"], preferred_element_type=jnp.float32)
        order, pair_gate, sizes = route_held(logits, cfg)
        starts = jnp.cumsum(sizes) - sizes
        held = jnp.sum(sizes)
        xa = x.astype(act)

    def chunk(c):
        lo = c * t
        rows = jax.lax.dynamic_slice(order, (lo,), (t,))
        with jax.named_scope("moe.route"):
            tok = rows // m.top_k
            gs = jnp.clip(jnp.minimum(starts + sizes, lo + t)
                          - jnp.maximum(starts, lo), 0).astype(jnp.int32)
            # rows past the last group are left unwritten by the TPU's
            # grouped matmul, forward and backward: select them out on
            # both sides so that neither their values nor their
            # gradients reach a token
            valid = (jnp.arange(t) < jnp.sum(gs))[:, None]
            xs = jnp.where(valid, xa[tok], 0)
        with jax.named_scope("moe.experts"):
            g = jax.lax.ragged_dot(xs, bank["w_gate"], gs,
                                   preferred_element_type=jnp.float32)
            u = jax.lax.ragged_dot(xs, bank["w_up"], gs,
                                   preferred_element_type=jnp.float32)
            hid = (jax.nn.silu(g) * u).astype(act)
            out = jax.lax.ragged_dot(hid, bank["w_down"], gs,
                                     preferred_element_type=jnp.float32)
        with jax.named_scope("moe.route"):
            out = jnp.where(valid, out, 0.0) * pair_gate[rows][:, None]
            return jnp.zeros((t, d), jnp.float32).at[tok].add(out), \
                jnp.sum(gs)

    y = jnp.zeros((t, d), jnp.float32)
    done = jnp.int32(0)
    for c in range(m.top_k):
        yc, dc = jax.lax.cond(c * t < held, chunk,
                              lambda _: (jnp.zeros((t, d), jnp.float32),
                                         jnp.int32(0)), c)
        y, done = y + yc, done + dc
    if m.num_shared_experts:
        with jax.named_scope("moe.shared"):
            y = y + layers.frozen_swiglu(p["shared"], xa)
    counters = {"held_pairs": held, "dropped_pairs": held - done,
                "max_per_expert": jnp.max(sizes),
                "mean_per_expert": held.astype(jnp.float32) / n}
    return y, counters

