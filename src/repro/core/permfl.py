"""PerMFL — Algorithm 1 of the paper, as a fully-jitted stacked simulator.

State layout ("stacked FL"): device models are a pytree whose leaves carry
leading axes (M, N, ...) — M teams x N devices — team models carry (M, ...),
and the global model is unstacked. Device-local steps are vmapped over
(M, N); team aggregation is a (masked) mean over N; global aggregation a
(masked) mean over M. Under pjit the (M, N) axes shard over the
(pod, data) mesh axes, which maps the paper's WAN/LAN communication
hierarchy onto DCN/ICI (DESIGN.md §2).

One call = one global round t:

    w_i^{t,0} = x^t
    repeat K:  theta^{k,0} = w^k;  L prox-SGD device steps (eq. 4, the
               fused kernel);  team update (eq. 9)
    x^{t+1} = (1 - beta*gamma) x^t + beta*gamma * mean_i w_i^{t,K}  (eq. 13)
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.comm import (CommConfig, CommState, compress_tree,
                        compress_tree_ef, init_comm_state)
from repro.kernels.interface import dispatch_key
from repro.kernels.prox_update import prox_sgd_tree


# The sweepable hyperparameters: the float knobs the paper's Fig 3 / §D.4
# grids vary. They are the pytree *leaves* of PerMFLHParams, so a jitted
# round traced once serves every value (and run_sweep can vmap a whole
# grid); the loop bounds (k_team, l_local) and the structural knobs
# (momentum, weight_decay — they select kernel branches) stay static.
SWEEPABLE_HPARAMS = ("alpha", "eta", "beta", "lam", "gamma")


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True)
class PerMFLHParams:
    """Algorithm 1 hyperparameters (paper §3 / Theorem 1 notation).

    A frozen dataclass registered as a pytree: the SWEEPABLE_HPARAMS
    floats flatten to traced leaves (so compiled rounds are shared across
    values and grids vmap), while k_team / l_local / momentum /
    weight_decay ride in the static treedef. Instances built from plain
    floats stay hashable and usable as cache keys.
    """
    alpha: float = 0.01      # device LR
    eta: float = 0.03        # team LR
    beta: float = 0.6        # server LR
    lam: float = 0.5         # device<->team proximity (lambda)
    gamma: float = 1.5       # team<->global proximity (gamma)
    k_team: int = 10         # K: team iterations per global round
    l_local: int = 20        # L: device iterations per team iteration
    momentum: float = 0.0    # optional heavy-ball on the device step
    weight_decay: float = 0.0

    def tree_flatten(self):
        """Sweepable floats as children; loop bounds/branch knobs as aux."""
        children = tuple(getattr(self, k) for k in SWEEPABLE_HPARAMS)
        aux = (self.k_team, self.l_local, self.momentum, self.weight_decay)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        k_team, l_local, momentum, weight_decay = aux
        return cls(*children, k_team=k_team, l_local=l_local,
                   momentum=momentum, weight_decay=weight_decay)


@jax.tree_util.register_pytree_node_class
@dataclass
class PerMFLState:
    """x: global model; w: (M, ...); theta: (M, N, ...); comm: optional
    CommState (per-tier error-feedback residuals) when compression is on."""
    x: Any
    w: Any
    theta: Any
    round: jnp.ndarray  # scalar i32
    comm: Optional[CommState] = None

    def tree_flatten(self):
        return (self.x, self.w, self.theta, self.round, self.comm), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_state(params, m_teams: int, n_devices: int,
               comm: Optional[CommConfig] = None) -> PerMFLState:
    """All tiers initialized from a single model (Algorithm 1, init)."""
    def bc(x, lead):
        return jnp.broadcast_to(x[(None,) * len(lead)], lead + x.shape).copy()
    w = jax.tree.map(lambda p: bc(p, (m_teams,)), params)
    theta = jax.tree.map(lambda p: bc(p, (m_teams, n_devices)), params)
    cs = None if comm is None else init_comm_state(params, m_teams,
                                                   n_devices, comm)
    return PerMFLState(x=params, w=w, theta=theta, round=jnp.int32(0),
                       comm=cs)


def _keep_where(mask, new_tree, old_tree):
    """Leaf-wise participation gate: keep `new` where the leading-axes
    mask is set, else `old`. mask shape is a prefix of every leaf shape."""
    def leaf(n, o):
        m = mask.reshape(mask.shape + (1,) * (n.ndim - mask.ndim))
        return jnp.where(m > 0, n, o)
    return jax.tree.map(leaf, new_tree, old_tree)


def _masked_mean(tree, mask, axis, fallback=None):
    """Mean over `axis` weighted by mask; if the mask is all-zero along the
    axis, fall back to `fallback` (or the unmasked mean)."""
    denom = mask.sum(axis=axis)

    def leaf(x, fb):
        extra = x.ndim - mask.ndim
        m = mask.reshape(mask.shape + (1,) * extra)
        num = (x * m).sum(axis=axis)
        d = denom.reshape(denom.shape + (1,) * (num.ndim - denom.ndim))
        mean = num / jnp.maximum(d, 1.0)
        if fb is not None:
            take = (d > 0)
            mean = jnp.where(take, mean, fb)
        return mean

    if fallback is None:
        return jax.tree.map(lambda x: leaf(x, None), tree)
    return jax.tree.map(leaf, tree, fallback)


def normalize_masks(team_mask, device_mask, m_teams: int, n_devices: int):
    """None -> all-ones participation arrays. Masks always enter the jitted
    round as (M,) / (M, N) f32 arrays so a single trace serves every
    participation pattern (full rounds and team_frac<1 rounds alike)."""
    if team_mask is None:
        team_mask = jnp.ones((m_teams,), jnp.float32)
    if device_mask is None:
        device_mask = jnp.ones((m_teams, n_devices), jnp.float32)
    return jnp.asarray(team_mask, jnp.float32), \
        jnp.asarray(device_mask, jnp.float32)


def permfl_round(state: PerMFLState, data, hp: PerMFLHParams,
                 loss_fn: Callable, *, m_teams: int, n_devices: int,
                 team_mask=None, device_mask=None,
                 comm: Optional[CommConfig] = None, base=None):
    """One global round.

    data: pytree of arrays with leading (M, N, ...) — each device's (full)
        batch; loss_fn(params, device_batch) -> scalar.
    base: None, or a frozen model the tiers are fitted on top of (LoRA
        adapters over a pretrained base): it enters the compiled round as
        an argument, unstacked, and neither the prox step nor the means
        touch it. Passing a base (``{}`` where the loss needs no frozen
        weights) selects the stacked convention: loss_fn then takes
        every device at once,
        ``loss_fn(theta (M, N, ...), data (M, N, ...), base) -> (M, N)``,
        and the device gradients are those of the sum of its values
        (each device's loss depends on its own theta only).
    team_mask: (M,) f32 in {0,1}; device_mask: (M, N) f32. None = full
        participation (paper's default mode 1). Masks are normalized to
        arrays here, at the boundary, so flipping between None and arrays
        across rounds never re-traces the compiled round.
    comm: optional CommConfig. When given, the device->team theta deltas
        (each team iteration) and the team->server w deltas (once per
        round) cross their links compressed, with per-sender error
        feedback carried in state.comm; local/personalized models stay
        exact (DESIGN.md §3).
    """
    if comm is not None and state.comm is None:
        raise ValueError("comm config given but state carries no CommState; "
                         "build the state with init_state(..., comm=cfg)")
    team_mask, device_mask = normalize_masks(team_mask, device_mask,
                                             m_teams, n_devices)
    return _permfl_round(state, data, hp, loss_fn, m_teams=m_teams,
                         n_devices=n_devices, team_mask=team_mask,
                         device_mask=device_mask, comm=comm,
                         kdispatch=dispatch_key(), base=base)


# hp is NOT static: its float leaves trace, so one compiled round serves
# every hyperparameter value (fig3's 9-point grid used to pay 9 compiles)
# and run_sweep can vmap a stacked grid through the same program.
# kdispatch (the KernelType/fused pair from dispatch_key()) is a pure
# cache salt: kernel choices are read from the environment at trace time,
# so it must ride the jit key or flipping REPRO_KERNEL_MODE between
# calls would silently reuse a stale trace.
@functools.partial(
    jax.jit,
    static_argnames=("loss_fn", "m_teams", "n_devices", "comm", "kdispatch"))
def _permfl_round(state: PerMFLState, data, hp: PerMFLHParams,
                  loss_fn: Callable, *, m_teams: int, n_devices: int,
                  team_mask, device_mask,
                  comm: Optional[CommConfig] = None, kdispatch=None,
                  base=None):
    x = state.x
    if base is None:
        per_device_grad = jax.vmap(jax.vmap(jax.grad(loss_fn)))
    else:
        def per_device_grad(theta, d):
            return jax.grad(lambda t: jnp.sum(loss_fn(t, d, base)))(theta)
    if comm is not None:
        round_key = jax.random.fold_in(state.comm.key, state.round)
        # devices of masked-out teams may run locally but never transmit:
        # their EF residuals must not record undelivered messages, even if
        # the caller passed masks that disagree.
        ef_gate = device_mask * team_mask[:, None]

    def bcast_n(w):
        return jax.tree.map(
            lambda wl: jnp.broadcast_to(
                wl[:, None], (m_teams, n_devices) + wl.shape[1:]), w)

    def device_loop(theta, w):
        """L prox-SGD steps (eq. 4), vmapped over (M, N)."""
        anchor = bcast_n(w)

        def one_step(_, carry):
            theta, mom = carry
            with jax.named_scope("permfl.grad"):
                g = per_device_grad(theta, data)
            with jax.named_scope("permfl.prox"):
                theta, mom = prox_sgd_tree(
                    theta, g, anchor, mom, alpha=hp.alpha, lam=hp.lam,
                    momentum=hp.momentum, weight_decay=hp.weight_decay)
            return theta, mom

        mom0 = jax.tree.map(lambda t: jnp.zeros(t.shape, jnp.float32), theta)
        theta, _ = jax.lax.fori_loop(0, hp.l_local, one_step, (theta, mom0))
        return theta

    def run_devices(w):
        """Re-init theta from w (LAN downlink), L device steps."""
        theta = jax.tree.map(
            lambda wl: jnp.broadcast_to(
                wl[:, None], (m_teams, n_devices) + wl.shape[1:]).copy(), w)
        return device_loop(theta, w)

    def team_update(w, theta_bar):
        c = 1.0 - hp.eta * hp.lam - hp.eta * hp.gamma
        return jax.tree.map(
            lambda wl, xl, tb: c * wl + hp.eta * hp.gamma * xl[None]
            + hp.lam * hp.eta * tb,
            w, x, theta_bar)

    def team_iter(k, carry):
        """One team round: re-init theta from w, L device steps, eq. 9."""
        w, _ = carry
        theta = run_devices(w)
        with jax.named_scope("permfl.team"):
            theta_bar = _masked_mean(theta, device_mask, axis=1, fallback=w)
            w = team_update(w, theta_bar)
        return w, theta

    def team_iter_comm(k, carry):
        """team_iter with a compressed device->team uplink: each device
        ships C(theta - w + ef); the team aggregates the decompressed
        deltas on top of the anchor w it already holds. With error
        feedback on, the EF add and residual update are fused into the
        compression kernels (compress_tree_ef)."""
        w, _, ef_dev = carry
        theta = run_devices(w)
        anchor = bcast_n(w)
        kk = jax.random.fold_in(round_key, k)
        if comm.error_feedback:
            delta = jax.tree.map(lambda t, a: t - a, theta, anchor)
            chat, ef_new = compress_tree_ef(comm, kk, delta, ef_dev,
                                            (m_teams, n_devices))
            ef_dev = _keep_where(ef_gate, ef_new, ef_dev)
        else:
            msg = jax.tree.map(lambda t, a, e: t - a + e,
                               theta, anchor, ef_dev)
            chat = compress_tree(comm, kk, msg, (m_teams, n_devices))
        with jax.named_scope("permfl.team"):
            theta_hat = jax.tree.map(lambda a, ch: a + ch, anchor, chat)
            theta_bar = _masked_mean(theta_hat, device_mask, axis=1,
                                     fallback=w)
            w = team_update(w, theta_bar)
        return w, theta, ef_dev

    # w_i^{t,0} = x^t
    w0 = jax.tree.map(
        lambda xl: jnp.broadcast_to(xl[None], (m_teams,) + xl.shape).copy(), x)
    theta0 = state.theta
    if comm is None:
        w, theta = jax.lax.fori_loop(0, hp.k_team, team_iter, (w0, theta0))
    else:
        w, theta, ef_dev = jax.lax.fori_loop(
            0, hp.k_team, team_iter_comm, (w0, theta0, state.comm.ef_dev))

    with jax.named_scope("permfl.global"):
        # eq. 13 (global) — non-participating teams keep w out of the
        # average, and also do not move (their w snaps back to x next
        # round anyway).
        w_eff = _keep_where(team_mask, w, state.w)
        if comm is None:
            w_bar = _masked_mean(w_eff, team_mask, axis=0, fallback=x)
            comm_state = state.comm
        else:
            # team->server WAN uplink: each team ships C(w - x + ef); the
            # server reconstructs w_hat = x + C(...) against the x it holds.
            # Masked-out teams need no substitute value — the masked mean
            # zeroes their contribution.
            ef_team = state.comm.ef_team
            kk = jax.random.fold_in(round_key, hp.k_team)
            if comm.error_feedback:
                delta = jax.tree.map(lambda wl, xl: wl - xl[None], w, x)
                chat, ef_new = compress_tree_ef(comm, kk, delta, ef_team,
                                                (m_teams,))
                ef_team = _keep_where(team_mask, ef_new, ef_team)
            else:
                msg = jax.tree.map(lambda wl, xl, e: wl - xl[None] + e,
                                   w, x, ef_team)
                chat = compress_tree(comm, kk, msg, (m_teams,))
            w_hat = jax.tree.map(lambda xl, ch: xl[None] + ch, x, chat)
            w_bar = _masked_mean(w_hat, team_mask, axis=0, fallback=x)
            comm_state = CommState(ef_dev=ef_dev, ef_team=ef_team,
                                   key=state.comm.key)
        x_new = jax.tree.map(
            lambda xl, wb: (1.0 - hp.beta * hp.gamma) * xl
            + hp.beta * hp.gamma * wb, x, w_bar)

    # devices/teams that did not participate keep their previous theta/w
    th_eff = _keep_where(device_mask, theta, state.theta)

    return PerMFLState(x=x_new, w=w_eff, theta=th_eff,
                       round=state.round + 1, comm=comm_state)


# ---------------------------------------------------------------------------
# Evaluation helpers
# ---------------------------------------------------------------------------

def tier_norms(state: PerMFLState):
    """The drift quantities the paper's rates are stated in, per tier:
    ``(pers_gap, tier_drift)`` where ``pers_gap`` is the (M, N) matrix of
    personalization gaps ``||theta_ij - w_i||`` and ``tier_drift`` the
    (M,) vector of team-vs-server drifts ``||w_i - x||``. Traceable —
    the engine's probe path calls this inside the scanned round body."""
    from repro.obs.probes import stacked_sq_norm

    gap = jax.tree.map(lambda t, wl: t - wl[:, None], state.theta, state.w)
    drift = jax.tree.map(lambda wl, xl: wl - xl[None], state.w, state.x)
    return jnp.sqrt(stacked_sq_norm(gap, 2)), \
        jnp.sqrt(stacked_sq_norm(drift, 1))


def eval_stacked(state: PerMFLState, data, metric_fn, *, which: str = "pm",
                 base=None):
    """metric_fn(params, batch) -> scalar; data leading (M, N, ...).

    which: 'pm'  — per-device personalized models theta_ij on their data
           'tm'  — team models w_i on each device's data
           'gm'  — global model x on each device's data
    base: as in ``permfl_round``: metric_fn then takes every device at
        once, ``metric_fn(tiers (M, N, ...), data, base) -> (M, N)``.
    Returns (M, N) matrix of metric values.
    """
    if base is not None:
        lead = jax.tree.leaves(data)[0].shape[:2]
        tier = {"pm": state.theta,
                "tm": jax.tree.map(lambda w: jnp.broadcast_to(
                    w[:, None], lead + w.shape[1:]), state.w),
                "gm": jax.tree.map(lambda x: jnp.broadcast_to(
                    x, lead + x.shape), state.x)}[which]
        return metric_fn(tier, data, base)
    if which == "pm":
        return jax.vmap(jax.vmap(metric_fn))(state.theta, data)
    if which == "tm":
        f = jax.vmap(lambda w, d: jax.vmap(lambda dd: metric_fn(w, dd))(d))
        return f(state.w, data)
    if which == "gm":
        return jax.vmap(jax.vmap(lambda d: metric_fn(state.x, d)))(data)
    raise ValueError(which)
