"""Batched sweep engine: a whole hyperparameter/seed grid as ONE program.

The paper's empirical claims are sweeps — Fig 3 varies beta/gamma/lambda,
Tables 1/2 average over seeds — and the scanned engine (engine.py) still
dispatched them one configuration at a time: S sequential compiles+runs.
This module runs all S configurations in a single compiled program:

    jit( vmap over the (S,) config axis:
           chunked scan over rounds (the engine's round program, verbatim)
         -> per-config metric histories, final states, realized counts )

What makes this possible is the hyperparameter split (`tree_hparams` on
every FLAlgorithm): float hyperparameters are *sweepable leaves* that
stack into (S,) f32 arrays and trace, while loop bounds, loss functions,
and branch-selecting knobs stay static structure shared by every config.
Each vmap lane rebuilds its own algorithm instance from its slice of the
stacked leaves — same round code, S sets of values, one XLA program.

Seeds ride the same axis. A seed contributes (a) the in-graph
participation-sampling PRNG chain (exactly run_experiment's) and
(b) optionally the model init, when ``params0`` is a callable
``seed -> params`` evaluated per config on the host.

System profiles (`repro.system.SystemSpec`) ride the axis too: a spec
splits into float leaves exactly like hyperparameters
(``tree_floats``), so ``system=[...]`` stacks several wall-clock worlds
— LAN campus vs cellular WAN vs IoT edge — into (S,) operands of the
same program, and each config comes back with its own simulated
`Timeline` (DESIGN.md §8). For grids whose *static* structure differs —
e.g. different compressors, which change the round graph itself —
``run_multi_sweep`` fuses several prepared sweeps into one jitted
program so they still cost a single dispatch.

On hardware, the (S,) axis shards over the mesh's ``sweep`` axis — the
repurposed pod/DCN tier, since configs never communicate. The program
runs under ``shard_map``, each device vmapping over its own block of
configs, because GSPMD cannot partition the Pallas kernels inside the
round; each config's (M, N) state stays whole on its device, so the
mesh's data and model axes must have size 1; see
``launch.mesh.make_sweep_mesh`` / ``sharding.specs.sweep_pspecs`` and
DESIGN.md §6. Byte accounting stays on the host: realized participation
counts come back per config and feed one CommLedger each.
"""
from __future__ import annotations

import functools
import itertools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels.interface import dispatch_key
from repro.obs.events import write_sweep
from repro.obs.health import HealthReport
from repro.obs.spans import SpanLog, current_log, span
from repro.obs.trace import RunTrace, TraceConfig
from repro.system import get_profile
from repro.train.engine import (_METRIC_FIELDS, FLResult,
                                assemble_timeline, _chunk_runner,
                                check_participation, hparam_skeleton)

__all__ = ["FLSweepResult", "grid_product", "run_multi_sweep", "run_sweep"]


def grid_product(**axes) -> list:
    """Cartesian product of named value lists as a list of config dicts.

    ``grid_product(beta=[0.1, 0.5], lam=[1.0])`` ->
    ``[{"beta": 0.1, "lam": 1.0}, {"beta": 0.5, "lam": 1.0}]``.
    """
    names = list(axes)
    return [dict(zip(names, vals))
            for vals in itertools.product(*axes.values())]


@dataclass
class FLSweepResult:
    """One vmapped sweep: S = len(grid) * len(seeds) * len(profiles)
    configurations.

    configs: resolved per-config dicts — every sweepable hyperparameter
        plus the config's ``seed`` (and ``system`` profile name when
        system models ride the axis) — in grid-major order (all seeds of
        grid[0], then grid[1], ...; profiles innermost).
    results: one FLResult per config (trajectories, final state slice,
        participation, per-config CommLedger and Timeline). Wall times
        on each FLResult are the sweep's, amortized over S, with the
        same ``seconds = compile_seconds + run_seconds`` split as
        ``run_experiment``.
    state_stacked: final-state pytree with the leading (S,) config axis
        intact (sharded over the mesh's sweep axis when one was given).
    dispatches: jitted calls that executed the whole sweep (1, or 2 when
        rounds % eval_every != 0 leaves a remainder chunk).
    """
    configs: list = field(default_factory=list)
    results: list = field(default_factory=list)
    state_stacked: Any = None
    seconds: float = 0.0
    compile_seconds: float = 0.0
    run_seconds: float = 0.0
    dispatches: int = 0
    events_path: Optional[str] = None    # JSONL event log (trace_dir runs)

    def __len__(self):
        return len(self.results)

    def __getitem__(self, i) -> FLResult:
        return self.results[i]

    def __iter__(self):
        return iter(self.results)

    def best(self, which="pm") -> list:
        """Per-config best metric (see FLResult.best)."""
        return [r.best(which) for r in self.results]

    def final(self, which="pm") -> list:
        """Per-config final-eval metric."""
        return [r.last(which) for r in self.results]


# One compiled program per (hparam skeleton, metric_fn, dims,
# participation, system skeleton) — every grid/seed/profile stacking
# with matching static structure reuses it, whatever the hyperparameter
# or system values are (they are traced operands), and each vmap lane
# runs the engine's chunk program (_chunk_runner) verbatim.
@functools.lru_cache(maxsize=64)
def _sweep_program(skel, metric_fn, m, n, team_frac, device_frac,
                   sys_key=None, trace=None, kdispatch=None, cohort=None,
                   mesh=None):
    run_chunks = _chunk_runner(skel, metric_fn, m, n, team_frac,
                               device_frac, sys_key, trace, cohort)

    def lanes(hstack, states, keys, sstack, tr, va, *, length, n_steps):
        """vmap over the (S,) axis of (hstack, states, keys[, sstack])."""
        if sys_key is None:
            return jax.vmap(lambda h, s, k: run_chunks(
                h, s, k, tr, va, length=length, n_steps=n_steps))(
                    hstack, states, keys)
        return jax.vmap(lambda h, s, k, sl: run_chunks(
            h, s, k, tr, va, sleaves=sl, length=length,
            n_steps=n_steps))(hstack, states, keys, sstack)

    @functools.partial(jax.jit, static_argnames=("length", "n_steps"))
    def swept(hstack, states, keys, sstack, tr, va, *, length, n_steps):
        body = functools.partial(lanes, length=length, n_steps=n_steps)
        if mesh is None:
            return body(hstack, states, keys, sstack, tr, va)
        # GSPMD cannot partition a Pallas (Mosaic) kernel, so each device
        # runs its own block of configs by hand; configs never talk, so
        # the body needs no collective
        cfg = P("sweep")
        return jax.shard_map(
            body, mesh=mesh, in_specs=(cfg, cfg, cfg, cfg, P(), P()),
            out_specs=cfg, check_vma=False)(
                hstack, states, keys, sstack, tr, va)

    return swept


def _stack_trees(trees):
    """[pytree, ...] -> one pytree with a leading (S,) axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


@dataclass
class _Prepared:
    """One sweep's validated, stacked operands + static program key."""
    algo: Any
    skel: Any
    sys_key: Any               # (SystemSpec skeleton, RoundWorkload) | None
    team_frac: float
    device_frac: float
    hstack: dict
    sstack: Optional[dict]
    states: Any
    keys: Any
    configs: list
    profiles: list             # per-combo SystemSpec | None
    ledger_params: Any


def _prepare(algo, grid, seeds, params0, m, n, team_frac, device_frac,
             system) -> _Prepared:
    """Validate one sweep and stack its (S,) operands (shared by
    run_sweep and run_multi_sweep)."""
    if isinstance(grid, dict):
        grid = grid_product(**grid)
    grid = [dict(g) for g in grid]
    if not grid:
        raise ValueError("empty grid: pass [{}] for a seeds-only sweep")
    if isinstance(seeds, int):
        seeds = (seeds,)
    seeds = tuple(int(s) for s in seeds)
    if not seeds:
        raise ValueError("empty seeds: pass at least one PRNG seed")
    check_participation(algo, team_frac, device_frac)

    if system is None:
        profiles = [None]
    else:
        if isinstance(system, (str, dict)) or not isinstance(
                system, (list, tuple)):
            system = [system]
        profiles = [get_profile(p) for p in system]
        # unreachable today — every SystemSpec skeleton zeroes the same
        # all-float fields — but guards the day the spec grows static
        # structure (e.g. a distribution-kind switch), which would
        # silently compile the wrong program for mixed profiles
        skels = {p.skeleton() for p in profiles}
        if len(skels) != 1:
            raise ValueError(
                "system profiles on one sweep axis must share a static "
                f"skeleton; got {len(skels)} distinct ones")

    leaves0, _ = algo.tree_hparams()
    for g in grid:
        unknown = set(g) - set(leaves0)
        if unknown:
            raise ValueError(
                f"unknown sweepable hyperparameter(s) {sorted(unknown)}; "
                f"{type(algo).__name__} sweeps over {sorted(leaves0)}")

    combos = [(g, s, p) for g in grid for s in seeds for p in profiles]
    configs = [dict(leaves0, **g, seed=s,
                    **({"system": p.name} if p is not None else {}))
               for g, s, p in combos]
    hstack = {k: jnp.asarray([float(dict(leaves0, **g)[k])
                              for g, _, _ in combos], jnp.float32)
              for k in leaves0}
    keys = jnp.stack([jax.random.PRNGKey(s) for _, s, _ in combos])

    sys_key = sstack = None
    if profiles[0] is not None:
        sys_leaves = [p.tree_floats()[0] for _, _, p in combos]
        sstack = {k: jnp.asarray([sl[k] for sl in sys_leaves], jnp.float32)
                  for k in sys_leaves[0]}

    if callable(params0):
        p_by_seed = {s: params0(s) for s in seeds}
        # one init per seed, however many grid points share it
        st_by_seed = {s: algo.init_state(p_by_seed[s], m, n)
                      for s in seeds}
        states = _stack_trees([st_by_seed[s] for _, s, _ in combos])
        ledger_params = p_by_seed[seeds[0]]
    else:
        state0 = algo.init_state(params0, m, n)
        S = len(combos)
        states = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (S,) + x.shape), state0)
        ledger_params = params0

    if profiles[0] is not None:
        from repro.system import workload_for
        sys_key = (profiles[0].skeleton(),
                   workload_for(algo, ledger_params))

    skel, _ = hparam_skeleton(algo)
    return _Prepared(algo=algo, skel=skel, sys_key=sys_key,
                     team_frac=team_frac, device_frac=device_frac,
                     hstack=hstack, sstack=sstack, states=states,
                     keys=keys, configs=configs,
                     profiles=[p for _, _, p in combos],
                     ledger_params=ledger_params)


def _collect(prep: _Prepared, states, metric_hist, outs_hist, *,
             seconds, compile_seconds, run_seconds, dispatches, rounds,
             eval_every, trace=None, cohort=None,
             population=None) -> FLSweepResult:
    """Slice one sweep's stacked outputs into per-config FLResults.

    metric_hist: field -> list of (S, n_steps) arrays; outs_hist: list of
    per-segment dicts of (S, n_steps, length) per-round output arrays.
    trace: the sweep's TraceConfig — when set, each config's ``probe:``
    output streams become a per-config `RunTrace` (and its ``health:``
    streams a per-config `HealthReport`, checked immediately per config
    under ``trace.fail_fast``).
    cohort/population: the sweep's virtualized-engine dims, recorded on
    each FLResult; per-config ``cohort_idx`` streams land in
    ``FLResult.cohort_indices``.
    """
    S = len(prep.configs)
    out = FLSweepResult(configs=prep.configs, state_stacked=states,
                        seconds=seconds, compile_seconds=compile_seconds,
                        run_seconds=run_seconds, dispatches=dispatches)
    for i in range(S):
        res = FLResult(seconds=seconds / S,
                       compile_seconds=compile_seconds / S,
                       run_seconds=run_seconds / S, rounds=rounds,
                       eval_every=eval_every, dispatches=dispatches,
                       cohort=cohort, population=population)
        for k, segs in metric_hist.items():
            getattr(res, _METRIC_FIELDS[k]).extend(
                float(x) for seg in segs for x in seg[i])
        flat = {}
        for seg in outs_hist:
            for k, v in seg.items():
                if k == "cohort_idx":
                    arr = np.asarray(v[i])
                    res.cohort_indices.extend(
                        arr.reshape((-1,) + arr.shape[-2:]).astype(int)
                        .tolist())
                    continue
                flat.setdefault(k, []).extend(v[i].reshape(-1).tolist())
        if trace is not None:
            res.trace = RunTrace(config=trace, series={
                k.split(":", 1)[1]: flat.pop(k)
                for k in sorted(flat) if k.startswith("probe:")})
            if trace.health:
                res.health = HealthReport(series={
                    k.split(":", 1)[1]: flat.pop(k)
                    for k in sorted(flat) if k.startswith("health:")})
                if trace.fail_fast:
                    res.health.check(f"config {i}")
        res.participation = list(zip([int(x) for x in flat["teams"]],
                                     [int(x) for x in flat["devices"]]))
        if "t_round" in flat:
            assemble_timeline(res, prep.profiles[i].name, flat["t_round"],
                              flat["dropped_teams"],
                              flat["dropped_devices"], rounds, eval_every)
        res.state = jax.tree.map(lambda x: x[i], states)
        ledger = prep.algo.make_ledger(prep.ledger_params)
        if ledger is not None:
            for n_teams, n_devices in res.participation:
                prep.algo.log_comm_round(ledger, n_teams=n_teams,
                                         n_devices=n_devices)
            res.comm = ledger
        out.results.append(res)
    return out


def run_sweep(algo, grid, seeds, params0, train_data, val_data, *,
              metric_fn: Callable, rounds: int, m: int, n: int,
              team_frac: float = 1.0, device_frac: float = 1.0,
              eval_every: int = 1, mesh=None, system=None, trace=None,
              trace_dir=None, event_meta=None,
              cohort: Optional[int] = None) -> FLSweepResult:
    """Run ``len(grid) * len(seeds) [* len(system)]`` experiments as one
    compiled program.

    algo: the template FLAlgorithm instance — its float hyperparameters
        (``algo.tree_hparams()``) are the sweepable names; static config
        (loop bounds, loss_fn, comm) is shared by every configuration.
    grid: list of {hparam: value} overrides, one per grid point (dicts may
        set different keys — unset names keep the template's value), or a
        {name: [values...]} dict taken as the full cartesian product.
    seeds: int or sequence of ints; every grid point runs once per seed.
        The seed drives the in-graph participation-sampling chain exactly
        as ``run_experiment(seed=...)`` does.
    params0: initial (unstacked) model pytree shared by all configs, or a
        callable ``seed -> params`` for per-seed inits (multi-seed tables).
    mesh: optional Mesh with a ``sweep`` axis, its other axes of size 1
        — the (S,) config axis is split across it and each device runs
        its own configs (``launch.mesh.make_sweep_mesh``). S must be a
        multiple of the sweep axis size.
    system: optional wall-clock model(s): one SystemSpec / profile name /
        spec dict, or a sequence of them — a sequence adds a *system
        profile* axis to the sweep (innermost), every profile sharing the
        compiled program via its float-leaf split. Each config's FLResult
        gains a simulated `Timeline` + `sim_seconds`.
    trace: optional `repro.obs.TraceConfig` (or True): probe scalars ride
        the vmapped scan outputs and each config's FLResult gains its own
        `RunTrace` — identical streams to running the config alone.
    trace_dir / event_meta: when set, write the whole sweep's JSONL event
        stream (sweep_header + per-config run sections) into trace_dir.
    cohort: optional cohort width — every config runs on the virtualized
        cohort engine (`run_experiment(cohort=...)`) with its own
        per-config device-state store riding the vmap axis.
    Remaining arguments match ``run_experiment``.

    Returns an FLSweepResult; equivalence with the sequential loop
    ``[run_experiment(rebuild(cfg), ...) for cfg in configs]`` is pinned
    by tests/test_sweep.py.
    """
    kw = dict(metric_fn=metric_fn, rounds=rounds, m=m, n=n,
              team_frac=team_frac, device_frac=device_frac,
              eval_every=eval_every, mesh=mesh, system=system,
              trace=trace, trace_dir=trace_dir, event_meta=event_meta,
              cohort=cohort)
    # span-log ownership mirrors run_experiment: outermost trace_dir
    # caller creates and saves; an already-active log absorbs our spans
    if trace_dir is None or current_log() is not None:
        return _run_sweep(algo, grid, seeds, params0, train_data,
                          val_data, **kw)
    tag = f"sweep-{getattr(algo, 'name', None) or 'run'}"
    log = SpanLog(meta={"kind": "sweep", "algo": getattr(algo, "name",
                                                         None)})
    with log.activate():
        try:
            return _run_sweep(algo, grid, seeds, params0, train_data,
                              val_data, **kw)
        finally:
            log.save(trace_dir, tag=tag)


def _run_sweep(algo, grid, seeds, params0, train_data, val_data, *,
               metric_fn, rounds, m, n, team_frac, device_frac,
               eval_every, mesh, system, trace, trace_dir, event_meta,
               cohort) -> FLSweepResult:
    if trace is True:
        trace = TraceConfig()
    if cohort is not None:
        cohort = int(cohort)
        if not 1 <= cohort <= n:
            raise ValueError(
                f"cohort must be in [1, n_devices={n}], got {cohort}")
    with span("build", algo=getattr(algo, "name", "?"), m=m, n=n,
              rounds=rounds):
        prep = _prepare(algo, grid, seeds, params0, m, n, team_frac,
                        device_frac, system)
    states, keys, hstack, sstack = (prep.states, prep.keys, prep.hstack,
                                    prep.sstack)

    if mesh is not None:
        from repro.sharding.specs import sweep_pspecs, to_named

        wide = {a: k for a, k in mesh.shape.items()
                if a != "sweep" and k > 1}
        if wide:
            raise ValueError(
                f"run_sweep splits configs over the mesh's sweep axis only;"
                f" its other axes must have size 1, got {wide}")
        if len(prep.configs) % mesh.shape["sweep"]:
            raise ValueError(
                f"{len(prep.configs)} configs do not split evenly over a "
                f"sweep axis of {mesh.shape['sweep']} devices")

        def place(tree):
            specs = to_named(sweep_pspecs(tree, m=m, n=n), mesh,
                             shape_tree=tree)
            return jax.tree.map(jax.device_put, tree, specs)

        states, hstack = place(states), place(hstack)
        if sstack is not None:
            sstack = place(sstack)
        # keys are (S, 2) uint32: place explicitly — the shape heuristic
        # would mistake the 2 key words for a team axis when m == 2
        keys = jax.device_put(keys, NamedSharding(mesh, P("sweep", None)))
        repl = NamedSharding(mesh, P())
        train_data = jax.tree.map(lambda x: jax.device_put(x, repl),
                                  train_data)
        val_data = jax.tree.map(lambda x: jax.device_put(x, repl),
                                val_data)

    swept = _sweep_program(prep.skel, metric_fn, m, n, team_frac,
                           device_frac, prep.sys_key, trace,
                           dispatch_key(), cohort, mesh)
    n_chunks, rem = divmod(rounds, eval_every)

    metric_hist = {}           # field -> list of (S, n_steps) arrays
    outs_hist = []             # list of per-segment output dicts
    dispatches = 0
    t0 = time.time()
    t_first = None
    for length, n_steps in ((eval_every, n_chunks), (rem, 1)):
        if length == 0 or n_steps == 0:
            continue
        first = t_first is None
        with span("dispatch", configs=len(prep.configs), chunks=n_steps):
            (states, keys), (metrics, outs) = swept(
                hstack, states, keys, sstack, train_data, val_data,
                length=length, n_steps=n_steps)
            if first:
                jax.block_until_ready(states)
                t_first = time.time()
        dispatches += 1
        for k, v in metrics.items():
            metric_hist.setdefault(k, []).append(np.asarray(v))
        outs_hist.append({k: np.asarray(v) for k, v in outs.items()})
    t_end = time.time()
    t_first = t_first if t_first is not None else t_end

    with span("collect", configs=len(prep.configs)):
        out = _collect(prep, states, metric_hist, outs_hist,
                       seconds=t_end - t0, compile_seconds=t_first - t0,
                       run_seconds=t_end - t_first, dispatches=dispatches,
                       rounds=rounds, eval_every=eval_every, trace=trace,
                       cohort=cohort,
                       population=n if cohort is not None else None)
    if trace_dir is not None:
        out.events_path = str(write_sweep(
            trace_dir, out, algo=algo,
            meta={"m": m, "n": n, "team_frac": team_frac,
                  "device_frac": device_frac, **(event_meta or {})}))
    return out


# Fused multi-sweep programs are cached per tuple of member static keys:
# each member's chunk program is inlined into one jitted body, so N
# structurally-different sweeps (e.g. different compressors) still cost
# one dispatch per segment.
@functools.lru_cache(maxsize=32)
def _multi_program(member_keys, metric_fn, m, n, kdispatch=None):
    runners = [_chunk_runner(skel, metric_fn, m, n, tf, df, sys_key,
                             trace, cohort)
               for skel, sys_key, tf, df, trace, cohort in member_keys]

    @functools.partial(jax.jit, static_argnames=("length", "n_steps"))
    def multi(ops, tr, va, *, length, n_steps):
        outs = []
        for run_chunks, (h, st, k, sl) in zip(runners, ops):
            if sl is None:
                outs.append(jax.vmap(lambda h_, s_, k_, rc=run_chunks: rc(
                    h_, s_, k_, tr, va, length=length,
                    n_steps=n_steps))(h, st, k))
            else:
                outs.append(jax.vmap(
                    lambda h_, s_, k_, sl_, rc=run_chunks: rc(
                        h_, s_, k_, tr, va, sleaves=sl_, length=length,
                        n_steps=n_steps))(h, st, k, sl))
        return tuple(outs)

    return multi


def run_multi_sweep(variants, train_data, val_data, *,
                    metric_fn: Callable, rounds: int, m: int, n: int,
                    eval_every: int = 1) -> list:
    """Run several *structurally different* sweeps as ONE jitted program.

    ``run_sweep`` batches everything that differs only in float values
    (hyperparameters, seeds, system profiles) on one vmap axis; what it
    cannot batch is a change to the round graph itself — a different
    compressor, a different algorithm. This entry point takes a list of
    such sweeps, inlines each one's vmapped chunk program into a single
    jitted body, and dispatches them together: N compressors x P system
    profiles in one call (``benchmarks/fig_time_to_accuracy.py``).

    variants: sequence of dicts, each with keys ``algo`` and ``params0``
        plus optional ``grid`` (default ``[{}]``), ``seeds`` (default
        ``(0,)``), ``team_frac`` / ``device_frac`` (default 1.0),
        ``system``, ``trace``, and ``cohort`` (as in ``run_sweep`` —
        per-variant, so probed and probe-free — or virtualized and
        stacked — members can share the program). Data, metric_fn,
        rounds, and dims are shared — variants are views of one
        experiment family.

    Returns one FLSweepResult per variant, in order; every result
    reports the same ``dispatches`` count (1, or 2 with a remainder
    chunk) because the members executed together.
    """
    preps = []
    traces = []
    cohorts = []
    for v in variants:
        v = dict(v)
        preps.append(_prepare(
            v["algo"], v.get("grid", [{}]), v.get("seeds", (0,)),
            v["params0"], m, n, v.get("team_frac", 1.0),
            v.get("device_frac", 1.0), v.get("system")))
        t = v.get("trace")
        traces.append(TraceConfig() if t is True else t)
        c = v.get("cohort")
        cohorts.append(None if c is None else int(c))

    member_keys = tuple(
        (p.skel, p.sys_key, p.team_frac, p.device_frac, t, c)
        for p, t, c in zip(preps, traces, cohorts))
    multi = _multi_program(member_keys, metric_fn, m, n, dispatch_key())
    ops = tuple((p.hstack, p.states, p.keys, p.sstack) for p in preps)
    n_chunks, rem = divmod(rounds, eval_every)

    metric_hist = [{} for _ in preps]
    outs_hist = [[] for _ in preps]
    carries = None
    dispatches = 0
    t0 = time.time()
    t_first = None
    for length, n_steps in ((eval_every, n_chunks), (rem, 1)):
        if length == 0 or n_steps == 0:
            continue
        results = multi(ops, train_data, val_data, length=length,
                        n_steps=n_steps)
        if t_first is None:
            jax.block_until_ready(results)
            t_first = time.time()
        dispatches += 1
        carries = [carry for carry, _ in results]
        ops = tuple((h, st, k, sl) for (h, _, _, sl), (st, k) in
                    zip(ops, carries))
        for i, (_, (metrics, outs)) in enumerate(results):
            for k, v in metrics.items():
                metric_hist[i].setdefault(k, []).append(np.asarray(v))
            outs_hist[i].append({k: np.asarray(v)
                                 for k, v in outs.items()})
    t_end = time.time()
    t_first = t_first if t_first is not None else t_end

    n_total = sum(len(p.configs) for p in preps) or 1
    out = []
    for i, p in enumerate(preps):
        share = len(p.configs) / n_total
        out.append(_collect(
            p, carries[i][0] if carries else p.states, metric_hist[i],
            outs_hist[i], seconds=(t_end - t0) * share,
            compile_seconds=(t_first - t0) * share,
            run_seconds=(t_end - t_first) * share, dispatches=dispatches,
            rounds=rounds, eval_every=eval_every, trace=traces[i],
            cohort=cohorts[i],
            population=n if cohorts[i] is not None else None))
    return out
