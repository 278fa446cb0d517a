"""Scenario execution: build caching + the run/sweep entry points.

``run_scenario`` routes one scenario through the scanned engine
(`train.engine.run_experiment`, one compiled program per experiment);
``sweep_scenario`` routes a hyperparameter/seed grid through the vmapped
sweep (`train.sweep.run_sweep`, the whole grid as one program).

The compiled-program caches in both engines key on the *identity* of the
loss/metric closures (they ride inside the frozen algorithm instances).
This module therefore memoizes scenario materialization by
``FLScenario.canonical()`` — the spec-hash identity — so every run of
the same scenario (any seed, any rounds) reuses one set of closures, one
FederatedData, and one algorithm template, and the engines' caches hit
instead of retracing (DESIGN.md §7).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Callable, Optional

from repro.obs.spans import SpanLog, current_log, span
from repro.scenarios.registry import get_scenario
from repro.scenarios.spec import (FLScenario, fns_for, init_base, init_model,
                                  to_jax)
from repro.train.engine import FLResult, run_experiment
from repro.train.sweep import FLSweepResult, run_sweep

__all__ = ["ScenarioBuild", "build_scenario", "run_scenario",
           "sweep_scenario"]

# default for the run/sweep `system` argument: "not passed — keep the
# spec's own system model". Distinct from None, which explicitly
# disables simulation on a system-bearing spec.
_KEEP_SPEC_SYSTEM = object()


@dataclass
class ScenarioBuild:
    """Everything materialized from one (scenario, seed): the stacked
    data (host + device), resolved model config, shared loss/metric
    closures, the frozen algorithm instance, and the seed's params."""
    scenario: FLScenario
    fd: Any            # FederatedData (host numpy)
    config: Any        # PaperModelConfig
    train: Any         # stacked jnp train batch
    val: Any           # stacked jnp val batch
    loss_fn: Callable
    metric_fn: Callable
    algo: Any          # frozen FLAlgorithm template
    params0: Any       # model init for this seed
    base: Any = None   # frozen base under LoRA adapters (zoo models)

    @property
    def m(self) -> int:
        """M: number of teams."""
        return self.fd.m_teams

    @property
    def n(self) -> int:
        """N: devices per team."""
        return self.fd.n_devices


@functools.lru_cache(maxsize=32)
def _data(data_spec, data_seed: int):
    """One federated partition (host + device arrays) per (DataSpec,
    seed) — scenarios differing only in algorithm/comm/rounds (e.g. the
    seven Table-1 cells of one row) share it instead of re-partitioning
    and holding duplicate stacked arrays."""
    # the span only fires on a cache miss — exactly when data-build work
    # actually happens; memoized rebuilds show as scenario_build hits
    with span("data_build", seed=data_seed):
        fd = data_spec.build(data_seed)
        train, val = to_jax(fd)
    return fd, train, val


@functools.lru_cache(maxsize=16)
def _fns(cfg):
    """One (loss, metric) closure pair per resolved model config. Shared
    closure identity across scenarios is what lets equal algorithm
    instances (same hparams, same loss object) hit one compiled
    program in the engine caches."""
    return fns_for(cfg)


@functools.lru_cache(maxsize=128)
def _materialize(canon: FLScenario):
    """Resolved build for one canonical spec, composed from the shared
    data/closure caches (the per-spec part — the frozen algorithm
    template — is tiny)."""
    fd, train, val = _data(canon.data, canon.data_seed)
    cfg = canon.model_config()
    loss, metric = _fns(cfg)
    algo = canon.algo.build(loss, comm=canon.comm)
    return fd, cfg, train, val, loss, metric, algo


@functools.lru_cache(maxsize=512)
def _params0(cfg, seed: int):
    return init_model(cfg, seed)


@functools.lru_cache(maxsize=4)
def _base(cfg):
    """One frozen base per zoo configuration, shared by every seed."""
    return init_base(cfg)


def build_scenario(name_or_spec, seed: int = 0) -> ScenarioBuild:
    """Materialize a scenario (registry name, spec dict, or FLScenario)
    for model-init seed ``seed``.

    Memoized on ``(spec_hash identity, seed)``: repeated builds return
    the same data arrays and the same closure/algorithm objects, which
    is what keys the engine's compiled-program cache across calls.
    """
    s = get_scenario(name_or_spec)
    # the system model is pure measurement — it never changes what gets
    # built, so strip it from the cache key: every profile of one
    # scenario shares data, closures, and the algorithm template
    canon = dataclasses.replace(s.canonical(), system=None)
    hits0 = _materialize.cache_info().hits
    with span("scenario_build", scenario=s.name, seed=seed) as sp:
        fd, cfg, train, val, loss, metric, algo = _materialize(canon)
        sp.set(memoized=_materialize.cache_info().hits > hits0)
        params0 = _params0(cfg, seed)
    return ScenarioBuild(scenario=s, fd=fd, config=cfg, train=train,
                         val=val, loss_fn=loss, metric_fn=metric,
                         algo=algo, params0=params0, base=_base(cfg))


def run_scenario(name_or_spec, *, rounds: Optional[int] = None,
                 seed: int = 0, init_seed: Optional[int] = None,
                 eval_every: int = 1, scan: bool = True,
                 system=_KEEP_SPEC_SYSTEM, trace=None,
                 trace_dir=None) -> FLResult:
    """Run one scenario through the scanned engine.

    rounds: override the spec's default round budget.
    seed: drives the in-graph participation-sampling PRNG chain and (by
        default) the model init.
    init_seed: separate model-init seed when it must differ from the
        participation seed (fig4 reproduces the paper this way).
    system: wall-clock model (SystemSpec / profile name / spec dict)
        overriding the scenario's own ``system`` field; pass None to
        disable simulation on a system-bearing spec. Unpassed, the
        spec's own model (if any) applies.
    trace / trace_dir: run-telemetry (`repro.obs`) — probe streams on
        ``FLResult.trace`` (health detectors on ``FLResult.health``), a
        JSONL event log whose header carries the scenario identity
        (name, family, spec_hash), and one Chrome-trace span file
        covering the scenario build plus the engine's
        build/dispatch/eval phases.
    Remaining arguments match ``train.engine.run_experiment``.
    """
    s = get_scenario(name_or_spec)
    # span-log ownership: run_scenario is the outermost layer here, so
    # the scenario-build spans and the engine's spans share one file
    own_log = SpanLog(meta={"kind": "scenario", "scenario": s.name}) \
        if trace_dir is not None and current_log() is None else None
    with contextlib.ExitStack() as stack:
        if own_log is not None:
            stack.enter_context(own_log.activate())
            stack.callback(own_log.save, trace_dir, s.name)
        b = build_scenario(s, seed if init_seed is None else init_seed)
        return run_experiment(
            b.algo, b.params0, b.train, b.val, metric_fn=b.metric_fn,
            rounds=s.rounds if rounds is None else rounds, m=b.m, n=b.n,
            team_frac=s.team_frac, device_frac=s.device_frac, seed=seed,
            eval_every=eval_every, scan=scan, cohort=s.cohort_size,
            system=s.system if system is _KEEP_SPEC_SYSTEM else system,
            trace=trace, trace_dir=trace_dir, base=b.base,
            event_meta={"scenario": s.name, "family": s.family,
                        "spec_hash": s.spec_hash()})


def sweep_scenario(name_or_spec, grid=({},), seeds=(0,), *,
                   rounds: Optional[int] = None, eval_every: int = 1,
                   mesh=None, system=_KEEP_SPEC_SYSTEM, trace=None,
                   trace_dir=None) -> FLSweepResult:
    """Run a hyperparameter grid x seeds over one scenario as a single
    vmapped program (``train.sweep.run_sweep``).

    grid: list of {hparam: value} overrides on the scenario algorithm's
        sweepable floats (or a {name: [values...]} product dict); pass
        ``[{}]`` for a seeds-only sweep.
    seeds: each seed gets its own model init (the tables' multi-seed
        protocol) and participation chain; the shared data comes from
        the spec's ``data_seed``.
    system: wall-clock model(s) — one profile, or a sequence batching a
        *system profile axis* into the same dispatch (run_sweep); None
        disables simulation on a system-bearing spec, and unpassed the
        scenario's own ``system`` field applies.
    trace / trace_dir: run-telemetry (`repro.obs`), as in run_scenario —
        per-config RunTraces and one sweep JSONL event file.
    """
    s = get_scenario(name_or_spec)
    if isinstance(seeds, int):
        seeds = (seeds,)
    seeds = tuple(int(x) for x in seeds)
    own_log = SpanLog(meta={"kind": "scenario_sweep",
                            "scenario": s.name}) \
        if trace_dir is not None and current_log() is None else None
    with contextlib.ExitStack() as stack:
        if own_log is not None:
            stack.enter_context(own_log.activate())
            stack.callback(own_log.save, trace_dir, f"sweep-{s.name}")
        b = build_scenario(s, seeds[0] if seeds else 0)
        if b.base is not None:
            raise ValueError(f"{s.name}: the sweep takes no frozen base; "
                             "run each configuration with run_scenario")
        return run_sweep(
            b.algo, grid, seeds, lambda sd: _params0(b.config, int(sd)),
            b.train, b.val, metric_fn=b.metric_fn,
            rounds=s.rounds if rounds is None else rounds, m=b.m, n=b.n,
            team_frac=s.team_frac, device_frac=s.device_frac,
            eval_every=eval_every, mesh=mesh, cohort=s.cohort_size,
            system=s.system if system is _KEEP_SPEC_SYSTEM else system,
            trace=trace, trace_dir=trace_dir,
            event_meta={"scenario": s.name, "family": s.family,
                        "spec_hash": s.spec_hash()})
