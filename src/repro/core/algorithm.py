"""Unified FL algorithm API — one protocol, seven implementations.

Every algorithm in the repo (PerMFL and the six Table-1 baselines) is a
stateless *instance* capturing its hyperparameters and loss function, and
exposing three pure methods the engine (`repro.train.engine`) drives:

    init_state(params, m, n)       -> state pytree (stacked tiers)
    round(state, data, team_mask=, device_mask=) -> new state
    eval(state, train_data, val_data, metric_fn) -> {metric: scalar}

``round`` must be traceable: the engine calls it inside ``jax.lax.scan``
under a single ``jit``, so one compiled program covers the whole
experiment instead of one host dispatch per round. Masks are always (M,)
/ (M, N) f32 arrays (the engine normalizes/samples them in-graph);
algorithms without a participation notion ignore them. ``eval`` returns a
dict of scalar metrics (keys among "pm" / "tm" / "gm" / "train_loss") and
also runs traced, so it compiles once per experiment instead of being
re-dispatched eagerly every eval round.

Byte accounting stays on the host: algorithms that move compressed bytes
implement ``make_ledger`` / ``log_comm_round`` and the engine feeds them
the *realized* participation counts it emitted as scan outputs
(DESIGN.md §5).

Implementations are *frozen* dataclasses: the engine caches compiled
programs keyed on the instance, so configuration must be immutable —
change a hyperparameter by constructing a new instance, never by
mutating one (mutation raises FrozenInstanceError).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.comm import CommConfig, CommLedger, CommState
from repro.core import permfl as P
from repro.obs.health import nonfinite_count
from repro.obs.probes import (masked_max, masked_mean, stacked_sq_norm,
                              tree_diff_norm)

__all__ = ["FLAlgorithm", "FLAlgorithmBase", "PerMFL", "eval_global",
           "eval_personal"]


@runtime_checkable
class FLAlgorithm(Protocol):
    """Structural type the engine drives; see module docstring.

    Implementations are frozen dataclasses named by ``name``; state is an
    arbitrary pytree with stacked (M, ...) / (M, N, ...) tiers; masks are
    (M,) / (M, N) f32 participation arrays.
    """
    name: str

    def init_state(self, params, m: int, n: int) -> Any:
        """Build the initial state pytree from one model for M teams x N
        devices."""
        ...

    def round(self, state, data, *, team_mask, device_mask) -> Any:
        """One traceable global round: state + (M, N, ...) data batches +
        participation masks -> new state."""
        ...

    def eval(self, state, train_data, val_data,
             metric_fn: Callable) -> dict:
        """Traced metrics: {'pm'|'tm'|'gm'|'train_loss': scalar}."""
        ...


class FLAlgorithmBase:
    """Defaults: no participation support (round ignores the masks — the
    engine refuses team_frac/device_frac < 1 so FLResult.participation
    never reports sampling that didn't happen), no comm ledger, and a
    generic float-field hyperparameter split for sweeps."""

    supports_participation = False

    def make_ledger(self, params) -> Optional[CommLedger]:
        """Host-side byte ledger for this config, or None (no comm
        accounting). params: an (unstacked) model pytree giving the wire
        leaf sizes."""
        return None

    def log_comm_round(self, ledger: CommLedger, *, n_teams: int,
                       n_devices: int) -> None:
        """Account one round's bytes from realized (team-gated)
        participation counts. No-op unless the algorithm moves bytes."""
        pass

    def probe_round(self, prev_state, state, data, *, team_mask,
                    device_mask, trace):
        """Traced per-round scalar diagnostics (`repro.obs`): called by
        the engine's round body right after ``round`` when a
        `TraceConfig` is active, returning ``{name: f32 scalar}`` probe
        values that ride the scan outputs. Pure measurement — reads the
        states, never changes them.

        Default: the whole-state update norm (``trace.grads``).
        Algorithms with tiered state override to add drift / residual /
        loss probes.
        """
        out = {}
        if trace.grads:
            out["update_norm"] = tree_diff_norm(prev_state, state)
        return out

    def health_round(self, prev_state, state, data, *, team_mask,
                     device_mask, trace):
        """Traced per-round health detectors (`repro.obs.health`): called
        by the engine's round body when ``trace.health`` is on, returning
        ``{name: f32 scalar}`` values where > 0 means "this round is
        bad". Same purity contract as ``probe_round`` — detectors only
        read the states, so health-on is trajectory-bit-identical and
        health-off is program-byte-identical.

        Default: counts of non-finite entries in the post-round state
        and in the round's update (delta vs ``prev_state``) — the delta
        catches an inf-minus-inf that cancels back to a finite state.
        Algorithms with a cheap loss at hand override to add an
        explosion flag against ``trace.health_loss_max``.
        """
        delta = jax.tree.map(
            lambda a, b: jnp.asarray(b) - jnp.asarray(a)
            if jnp.issubdtype(jnp.asarray(a).dtype, jnp.inexact) else a,
            prev_state, state)
        return {"nonfinite_params": nonfinite_count(state),
                "nonfinite_update": nonfinite_count(delta)}

    def serving_params(self, state, team=None, device=None):
        """The model this algorithm serves to one principal — the export
        hook the personalized serving subsystem (`repro.serve.store`,
        DESIGN.md §12) builds its (team, device)-keyed `ModelStore`
        from. Tier selection by argument:

            serving_params(state)              -> global-tier model
            serving_params(state, t)           -> team t's model
            serving_params(state, t, d)        -> device (t, d)'s model

        ``team`` / ``device`` may be traced indices, so the exporter can
        vmap the hook over ``arange(m)`` x ``arange(n)`` and materialize
        whole tiers as one gather. Default: the state *is* one global
        model served to everybody (FedAvg / h-SGD / Per-FedAvg — the
        latter personalizes at eval time from data, which a parameter
        store cannot carry). Personalized algorithms override to route
        the personal tier.
        """
        return state

    def device_axes(self, state, m: int, n: int):
        """Which state leaves are device-tier, i.e. stacked (M, N, ...)
        per (team, device) — the split the virtualized cohort engine
        uses to decide what lives in the `DeviceStateStore` and rides
        each round's gather/scatter (DESIGN.md §11).

        Returns a pytree of bools matching ``state``'s structure: True
        leaves are gathered to cohort width per round, False leaves
        (team/global tiers, counters, PRNG keys) stay resident at full
        shape. The default is a shape heuristic — a leaf is device-tier
        iff its leading axes are exactly (m, n) — which is ambiguous
        when a trailing dimension collides with n, so stateful
        algorithms override it with their explicit tier split.
        """
        return jax.tree.map(
            lambda l: bool(getattr(l, "ndim", 0) >= 2
                           and l.shape[:2] == (m, n)), state)

    def tree_hparams(self):
        """Split this config into sweepable leaves vs static structure.

        Returns ``(leaves, rebuild)`` where ``leaves`` maps hyperparameter
        name -> float for every float field of the dataclass (ints — loop
        bounds — and callables stay static), and ``rebuild(values)``
        returns an equivalent instance with those fields replaced.
        ``rebuild`` accepts traced values, so ``run_sweep`` can stack a
        grid into (S,) arrays and vmap one compiled program over it; the
        rebuilt instance is only ever used inside that trace, never as a
        compilation-cache key.
        """
        # select by annotation, not value type: a float-annotated field
        # passed an int literal (lr=1) must still sweep; coercing also
        # keeps the hparam skeleton cache key value-normalized
        leaves = {f.name: float(getattr(self, f.name))
                  for f in dataclasses.fields(self)
                  if f.type in (float, "float")}

        def rebuild(values):
            return dataclasses.replace(self, **values)

        return leaves, rebuild


# ---------------------------------------------------------------------------
# metric helpers shared by the implementations
# ---------------------------------------------------------------------------

def eval_global(x, val_data, metric_fn):
    """Unstacked model x evaluated on every device's data; scalar mean."""
    return jax.vmap(jax.vmap(lambda d: metric_fn(x, d)))(val_data).mean()


def eval_personal(theta, val_data, metric_fn):
    """(M, N, ...) stacked models on their own devices' data; scalar mean."""
    return jax.vmap(jax.vmap(metric_fn))(theta, val_data).mean()


# ---------------------------------------------------------------------------
# PerMFL as an FLAlgorithm
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PerMFL(FLAlgorithmBase):
    """Algorithm 1 (core.permfl) behind the unified API.

    comm: optional CommConfig — uplinks cross compressed with per-sender
    error feedback; the engine accounts bytes via make_ledger /
    log_comm_round from realized (gated) participation counts.
    """
    loss_fn: Callable
    hp: P.PerMFLHParams
    comm: Optional[CommConfig] = None

    name = "permfl"
    supports_participation = True   # paper modes 1-4 (§3.1)

    def init_state(self, params, m: int, n: int) -> P.PerMFLState:
        """All tiers (x / w / theta) broadcast from one model; EF
        residuals zeroed when comm is configured."""
        return P.init_state(params, m, n, comm=self.comm)

    def round(self, state, data, *, team_mask, device_mask, base=None):
        """One Algorithm-1 global round (K team iters x L device steps);
        ``base``: a frozen model under the tiers (``permfl_round``)."""
        m, n = device_mask.shape
        return P.permfl_round(state, data, self.hp, self.loss_fn,
                              m_teams=m, n_devices=n, team_mask=team_mask,
                              device_mask=device_mask, comm=self.comm,
                              base=base)

    def tree_hparams(self):
        """Sweepable leaves live one level down, inside ``hp``: the
        SWEEPABLE_HPARAMS floats (alpha/eta/beta/lam/gamma). k_team and
        l_local are loop bounds, momentum/weight_decay kernel-branch
        selectors — all static structure."""
        leaves = {k: float(getattr(self.hp, k))
                  for k in P.SWEEPABLE_HPARAMS}

        def rebuild(values):
            return dataclasses.replace(
                self, hp=dataclasses.replace(self.hp, **values))

        return leaves, rebuild

    def eval(self, state, train_data, val_data, metric_fn, base=None):
        """PM/TM/GM mean accuracy over all devices + mean train loss."""
        if base is not None:
            return {which: P.eval_stacked(state, val_data, metric_fn,
                                          which=which, base=base).mean()
                    for which in ("pm", "tm", "gm")} | {
                "train_loss": self.loss_fn(state.theta, train_data,
                                           base).mean()}
        return {
            "pm": P.eval_stacked(state, val_data, metric_fn,
                                 which="pm").mean(),
            "tm": P.eval_stacked(state, val_data, metric_fn,
                                 which="tm").mean(),
            "gm": P.eval_stacked(state, val_data, metric_fn,
                                 which="gm").mean(),
            "train_loss": jax.vmap(jax.vmap(self.loss_fn))(
                state.theta, train_data).mean(),
        }

    def probe_round(self, prev_state, state, data, *, team_mask,
                    device_mask, trace):
        """PerMFL's full probe set on top of the generic update norm: the
        personalization gap and tier drift Theorems 1-2 bound (mean/max
        over participants), the post-round device gradient norm,
        per-tier error-feedback residual norms (compressed runs), and
        the participation-weighted train loss."""
        out = super().probe_round(prev_state, state, data,
                                  team_mask=team_mask,
                                  device_mask=device_mask, trace=trace)
        gated = device_mask * team_mask[:, None]
        if trace.drift:
            gap, drift = P.tier_norms(state)      # (M, N), (M,)
            out["pers_gap_mean"] = masked_mean(gap, gated)
            out["pers_gap_max"] = masked_max(gap, gated)
            out["tier_drift_mean"] = masked_mean(drift, team_mask)
            out["tier_drift_max"] = masked_max(drift, team_mask)
        if trace.grads:
            g = jax.vmap(jax.vmap(jax.grad(self.loss_fn)))(state.theta,
                                                           data)
            out["grad_norm"] = masked_mean(
                jnp.sqrt(stacked_sq_norm(g, 2)), gated)
        if trace.residuals and state.comm is not None:
            out["ef_dev_norm"] = masked_mean(
                jnp.sqrt(stacked_sq_norm(state.comm.ef_dev, 2)),
                gated)
            out["ef_team_norm"] = masked_mean(
                jnp.sqrt(stacked_sq_norm(state.comm.ef_team, 1)),
                team_mask)
        if trace.loss:
            losses = jax.vmap(jax.vmap(self.loss_fn))(state.theta, data)
            out["part_loss"] = masked_mean(losses, gated)
        return out

    def health_round(self, prev_state, state, data, *, team_mask,
                     device_mask, trace):
        """Generic nonfinite detectors plus a loss-explosion flag: the
        participation-weighted personalized train loss trips when it
        goes non-finite or exceeds ``trace.health_loss_max``."""
        out = super().health_round(prev_state, state, data,
                                   team_mask=team_mask,
                                   device_mask=device_mask, trace=trace)
        gated = device_mask * team_mask[:, None]
        losses = jax.vmap(jax.vmap(self.loss_fn))(state.theta, data)
        ploss = masked_mean(losses, gated)
        out["loss_exploded"] = (
            (~jnp.isfinite(ploss))
            | (ploss > trace.health_loss_max)).astype(jnp.float32)
        return out

    def serving_params(self, state, team=None, device=None):
        """Full three-tier serving: device (t, d) gets its personal
        ``theta[t, d]``, a team-only principal gets ``w[t]``, and the
        global tier is ``x`` — exactly the fallback ladder the serving
        store resolves unknown principals down (DESIGN.md §12)."""
        if team is None:
            return state.x
        if device is None:
            return jax.tree.map(lambda l: l[team], state.w)
        return jax.tree.map(lambda l: l[team, device], state.theta)

    def device_axes(self, state, m, n):
        """Explicit tier split (the shape heuristic would misfire when a
        model dimension collides with n): device models ``theta`` and
        per-device EF residuals ``ef_dev`` are device-tier; team models
        ``x``/``w``, the round counter, team residuals and the comm
        PRNG key stay resident."""
        comm = None
        if state.comm is not None:
            comm = CommState(
                ef_dev=jax.tree.map(lambda _: True, state.comm.ef_dev),
                ef_team=jax.tree.map(lambda _: False, state.comm.ef_team),
                key=False)
        return P.PerMFLState(
            x=jax.tree.map(lambda _: False, state.x),
            w=jax.tree.map(lambda _: False, state.w),
            theta=jax.tree.map(lambda _: True, state.theta),
            round=False, comm=comm)

    # -- byte accounting (host side) ----------------------------------------

    def make_ledger(self, params):
        """CommLedger sized from the model's leaf shapes; None when no
        compression is configured."""
        if self.comm is None:
            return None
        return CommLedger.for_params(self.comm, params)

    def log_comm_round(self, ledger, *, n_teams, n_devices):
        """Bill one round: K LAN uplinks per participating device, one WAN
        uplink per participating team (counts pre-gated by the engine)."""
        ledger.log_round(k_team=self.hp.k_team, n_teams=n_teams,
                         n_devices=n_devices)
