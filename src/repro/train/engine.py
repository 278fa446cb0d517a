"""Scanned multi-round FL engine: one compiled program per experiment.

The legacy drivers dispatched one jitted round at a time from Python and
re-traced eval on every call; at paper scale (hundreds of rounds x seven
algorithms x hyperparameter sweeps) the experiments were bottlenecked on
host dispatch, not hardware. This engine runs any `FLAlgorithm`
(core.algorithm) as a *single* jitted program:

    jit( scan over eval chunks:
           scan over eval_every rounds:
             sample participation masks in-graph (PRNG key in the carry)
             state = algo.round(state, data, masks)
             emit realized (gated) participation counts   # scan outputs
           metrics = algo.eval(state, ...)                # traced, cached
         -> metric history + per-round counts )

Participation sampling lives in the graph (core.participation), threading
the PRNG key through the scan carry — the same split-per-round chain the
legacy loop used, so trajectories match bit-for-bit. Byte accounting
stays on the host: the per-round team/device counts come back as scan
outputs and feed `CommLedger` post-hoc, counting only devices whose team
also participated (device_mask * team_mask[:, None] — the legacy loop's
ungated `dm.sum()` overcounted).

A wall-clock system model (`repro.system`) rides the same machinery:
when one is given, the round body simulates each round's duration along
the hierarchy's critical path (and, in deadline mode, thins the
participation masks by dropping stragglers *before* the algorithm round
runs), the simulated times come back as scan outputs exactly like the
gated mask counts, and the host assembles a `Timeline` next to the
`CommLedger` — `FLResult.sim_seconds` holds the cumulative simulated
time at each eval point, so accuracy-vs-seconds curves fall out.

``scan=False`` runs the same semantics as a per-round host-dispatch loop
(the legacy execution model) — kept for equivalence tests and for
benchmarks/bench_engine.py to quantify the dispatch win.

``cohort=c`` switches to the virtualized cohort engine (DESIGN.md §11):
the scan carry holds the device-tier store (`repro.train.store`) next
to the resident tiers, and each round samples a per-team index map
(`core.participation.sample_cohort`, PRNG stream salted off the round's
mask key so mask chains never move), gathers the cohort's data + device
state to (M, c), runs the unchanged algorithm round at cohort width,
and scatters the updated rows back. Participation masks, ledger counts,
the system round-time model and the probes all see the (M, c) cohort —
the population only ever exists as store rows. ``cohort=None`` (and,
bit-for-bit, ``cohort=n``) is the stacked full-population path.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import CommLedger
from repro.core.participation import sample_cohort, sample_masks
from repro.kernels.interface import dispatch_key
from repro.obs.events import write_run
from repro.obs.health import HealthReport
from repro.obs.profiling import compiled_cost, profile_ctx
from repro.obs.spans import SpanLog, current_log, install_gc_spans, span
from repro.obs.trace import RunTrace, TraceConfig, eval_points
from repro.system import (Timeline, get_profile, simulate_round,
                          workload_for)
from repro.train.store import (gather_cohort, scatter_cohort,
                               split_device_state)

__all__ = ["FLResult", "eval_points", "run_experiment"]


@dataclass
class FLResult:
    """One experiment's outcome: metric histories (one entry per eval
    point), wall time (compile vs steady-state split), final algorithm
    state, optional per-tier byte ledger and simulated-time `Timeline`,
    and realized (team-gated) per-round participation counts.

    ``seconds = compile_seconds + run_seconds`` always holds:
    ``compile_seconds`` is wall time until the first jitted dispatch
    returns — dominated by trace+compile on a cold program cache, by
    that dispatch's execution on a warm one — and ``run_seconds`` is
    everything after. A scanned experiment issues only 1-2 dispatches,
    so ``run_seconds`` is near 0 there (and on a cold cache a remainder
    chunk's own compile lands in it); steady-state throughput is a warm
    rerun's ``seconds`` (what benchmarks/bench_engine.py reports)."""
    pm_acc: list = field(default_factory=list)   # per-eval personalized acc
    tm_acc: list = field(default_factory=list)
    gm_acc: list = field(default_factory=list)
    train_loss: list = field(default_factory=list)
    seconds: float = 0.0                 # total wall time (compile + run)
    compile_seconds: float = 0.0         # first dispatch (trace/compile)
    run_seconds: float = 0.0             # post-first-dispatch remainder
    state: Any = None    # final algorithm state (set for every algorithm)
    comm: Optional[CommLedger] = None    # per-tier byte ledger (comm runs)
    participation: list = field(default_factory=list)  # (teams, devices)/rnd
    timeline: Optional[Timeline] = None  # per-round simulated clock
    sim_seconds: list = field(default_factory=list)  # cum sim time @ evals
    trace: Optional[RunTrace] = None     # per-round probe streams (obs)
    health: Optional[HealthReport] = None  # per-round detector streams
    rounds: int = 0                      # round budget this result ran
    eval_every: int = 1                  # eval cadence (aligns histories)
    dispatches: int = 0                  # jitted calls that executed it
    events_path: Optional[str] = None    # JSONL event log (trace_dir runs)
    cohort: Optional[int] = None         # cohort width (virtualized runs)
    population: Optional[int] = None     # resident devices/team (ditto)
    cohort_indices: list = field(default_factory=list)  # (M, C) idx / rnd

    def last(self, which="pm"):
        """Final-eval value of metric `which` ('pm'|'tm'|'gm'); NaN if the
        algorithm never reported it."""
        hist = {"pm": self.pm_acc, "tm": self.tm_acc, "gm": self.gm_acc}[which]
        return hist[-1] if hist else float("nan")

    def best(self, which="pm"):
        """Best eval value of metric `which` over the whole run."""
        hist = {"pm": self.pm_acc, "tm": self.tm_acc, "gm": self.gm_acc}[which]
        return max(hist) if hist else float("nan")


_METRIC_FIELDS = {"pm": "pm_acc", "tm": "tm_acc", "gm": "gm_acc",
                  "train_loss": "train_loss"}

# fold_in constant separating the system simulator's per-round PRNG
# stream from the participation-sampling stream (ASCII "SYST")
_SYSTEM_SALT = 0x53595354

# ditto for the cohort-sampling stream (ASCII "CHRT"): cohort indices are
# folded out of the round's mask key, never split off the carry chain, so
# running with any cohort_size — or none — leaves the mask and system
# streams bit-identical (pinned by tests/test_cohort_engine.py)
_COHORT_SALT = 0x43485254


def check_participation(algo, team_frac: float, device_frac: float):
    """Reject sampled participation for algorithms that ignore the masks —
    FLResult.participation must never report sampling that didn't gate
    anything. Shared by run_experiment and train.sweep.run_sweep."""
    if (team_frac < 1.0 or device_frac < 1.0) and \
            not getattr(algo, "supports_participation", False):
        raise ValueError(
            f"{getattr(algo, 'name', type(algo).__name__)} ignores "
            "participation masks; team_frac/device_frac < 1 would sample "
            "masks that never gate anything")


def _round_body(algo, m, n, team_frac, device_frac, system=None,
                trace=None, cohort=None, merge=None):
    """Scan step: in-graph mask sampling (key in the carry), optional
    system simulation (round time + deadline mask thinning), one
    algorithm round, and a dict of realized per-round outputs — gated
    participation counts, plus simulated time and straggler counts when
    a system model is active, plus ``probe:``-prefixed scalar
    diagnostics when a `TraceConfig` is (and ``health:``-prefixed
    detector values when its ``health`` flag is on too).

    system: None, or a static ``(SystemSpec skeleton, RoundWorkload)``
    pair; the spec's float values arrive as the traced ``sleaves``
    operand (see `repro.system.spec.SystemSpec.tree_floats`).
    trace: None (default — the emitted graph is byte-identical to the
    pre-trace engine), or a `TraceConfig`: ``algo.probe_round`` runs on
    the post-round state and its scalars ride the scan outputs.
    cohort: None for the stacked full-population body (carry is
    ``(state, key)``), or the cohort width: the carry becomes
    ``(dev_store, rest, key)`` (see `repro.train.store`), the round runs
    on the gathered (M, cohort) slice, and ``merge`` (from
    `split_device_state` at population width) rebuilds cohort states.
    Masks, system model and probes all run at cohort width, so
    participation/ledger counts and probe reductions cover exactly the
    materialized devices; the sampled index map rides the outputs as
    ``cohort_idx``.
    """
    sampled = team_frac < 1.0 or device_frac < 1.0
    nc = n if cohort is None else cohort

    def body(carry, _, data, sleaves=None, base=None):
        if cohort is None:
            state, key = carry
        else:
            dev, rest, key = carry
        if sampled:
            key, sub = jax.random.split(key)
            tm, dm = sample_masks(sub, m, nc, team_frac=team_frac,
                                  device_frac=device_frac)
        else:
            sub = None
            tm = jnp.ones((m,), jnp.float32)
            dm = jnp.ones((m, nc), jnp.float32)
        out = {}
        if cohort is not None:
            if sub is None:
                # full participation consumes no mask key; split one for
                # the cohort (and, below, the system) stream instead —
                # the split matches the stacked engine's unsampled
                # system split, so system streams stay bit-identical
                key, sub = jax.random.split(key)
            idx = sample_cohort(jax.random.fold_in(sub, _COHORT_SALT),
                                m, n, cohort)
            data = gather_cohort(data, idx)
            state = merge(gather_cohort(dev, idx), rest)
            out["cohort_idx"] = idx
        if system is not None:
            _, workload = system
            if sampled:
                # fold the system stream out of this round's mask key
                # instead of advancing the carry chain: the sampled mask
                # sequence stays bit-identical to a system-free run, so
                # a no-deadline system model is pure measurement under
                # every participation mode
                skey = jax.random.fold_in(sub, _SYSTEM_SALT)
            elif cohort is not None:
                skey = sub
            else:
                key, skey = jax.random.split(key)
            tm, dm, t_round, drop_t, drop_d = simulate_round(
                sleaves, workload, skey, tm, dm)
            out.update(t_round=t_round, dropped_teams=drop_t,
                       dropped_devices=drop_d)
        prev = state
        state = algo.round(state, data, team_mask=tm, device_mask=dm,
                           **_frozen(base))
        gated = dm * tm[:, None]
        out.update(teams=jnp.sum(tm).astype(jnp.int32),
                   devices=jnp.sum(gated).astype(jnp.int32))
        if trace is not None:
            probes = algo.probe_round(prev, state, data, team_mask=tm,
                                      device_mask=dm, trace=trace)
            out.update({f"probe:{k}": jnp.asarray(v, jnp.float32)
                        for k, v in probes.items()})
            if trace.health:
                checks = algo.health_round(prev, state, data,
                                           team_mask=tm, device_mask=dm,
                                           trace=trace)
                out.update({f"health:{k}": jnp.asarray(v, jnp.float32)
                            for k, v in checks.items()})
        if cohort is None:
            return (state, key), out
        cdev, crest, _ = split_device_state(algo, state, m, cohort)
        return (scatter_cohort(dev, idx, cdev), crest, key), out

    return body


def _frozen(base) -> dict:
    """The ``base=`` keyword of a round or eval, only when a frozen base
    is given: models without one trace exactly as before it existed."""
    return {} if base is None else {"base": base}


def hparam_skeleton(algo):
    """A value-independent cache key + the split for one algorithm: the
    instance with every sweepable float zeroed (hashable, shared by all
    hyperparameter values) plus its (leaves, rebuild) pair. Compiled
    programs key on the skeleton and take the float leaves as traced
    operands, so rerunning with new values never recompiles."""
    leaves, rebuild = algo.tree_hparams()
    return rebuild({k: 0.0 for k in leaves}), leaves


def _chunk_runner(skel, metric_fn, m, n, team_frac, device_frac,
                  system=None, trace=None, cohort=None):
    """The traceable heart of an experiment — shared verbatim by the
    per-experiment program below and train.sweep's vmapped grid program:
    rebuild the algorithm from its hparam leaves, then scan `n_steps`
    chunks of `length` rounds with a traced eval after each chunk.
    ``sleaves`` (the system model's float values, when `system` names a
    static skeleton/workload pair) is a traced operand like the hparam
    leaves — sweeps stack system profiles the same way they stack
    hyperparameters. ``trace`` (a static `TraceConfig` or None) selects
    the probe outputs the round body emits. ``cohort`` (static) splits
    the state into a device-tier store + resident rest for the inner
    scan — rounds run on gathered (M, cohort) slices, eval still sees
    the merged full-population state at each chunk boundary — and the
    external contract is unchanged: full state in, full state out."""
    _, rebuild = skel.tree_hparams()

    def run_chunks(hleaves, state, key, tr, va, *, sleaves=None, base=None,
                   length, n_steps):
        algo = rebuild(hleaves)
        if cohort is None:
            body = _round_body(algo, m, n, team_frac, device_frac, system,
                               trace)

            def chunk(carry, _):
                state, key = carry
                (state, key), outs = jax.lax.scan(
                    lambda c, x: body(c, x, tr, sleaves, base), (state, key),
                    length=length)
                with jax.named_scope("engine.eval"):
                    metrics = algo.eval(state, tr, va, metric_fn,
                                        **_frozen(base))
                return (state, key), (metrics, outs)

            return jax.lax.scan(chunk, (state, key), length=n_steps)

        dev, rest, merge = split_device_state(algo, state, m, n)
        body = _round_body(algo, m, n, team_frac, device_frac, system,
                           trace, cohort=cohort, merge=merge)

        def chunk(carry, _):
            carry, outs = jax.lax.scan(
                lambda c, x: body(c, x, tr, sleaves, base), carry,
                length=length)
            dev, rest, _ = carry
            with jax.named_scope("engine.eval"):
                metrics = algo.eval(merge(dev, rest), tr, va, metric_fn,
                                    **_frozen(base))
            return carry, (metrics, outs)

        (dev, rest, key), hist = jax.lax.scan(chunk, (dev, rest, key),
                                              length=n_steps)
        return (merge(dev, rest), key), hist

    return run_chunks


# Compiled programs are cached per (hparam skeleton, metric_fn, dims,
# system skeleton, trace config, kernel-dispatch key): every experiment
# with the same static structure — whatever its float hyperparameter or
# system-profile values — shares one compile and pays one dispatch. A
# TraceConfig is part of the static key (probes add scan outputs), so
# probes-off runs keep hitting the original program; the kernel-dispatch
# key (repro.kernels.interface.dispatch_key) rides the key the same way,
# so flipping REPRO_KERNEL_MODE / REPRO_COMPRESS_FUSED between runs
# re-traces instead of reusing a program that baked in the old kernels.
@functools.lru_cache(maxsize=128)
def _scan_program(skel, metric_fn, m, n, team_frac, device_frac,
                  system=None, trace=None, kdispatch=None, cohort=None):
    run_chunks = _chunk_runner(skel, metric_fn, m, n, team_frac,
                               device_frac, system, trace, cohort)
    return functools.partial(jax.jit, static_argnames=(
        "length", "n_steps"))(run_chunks)


@functools.lru_cache(maxsize=128)
def _eval_program(skel, metric_fn, kdispatch=None):
    _, rebuild = skel.tree_hparams()

    def evaluate(hleaves, state, tr, va, base=None):
        with jax.named_scope("engine.eval"):
            return rebuild(hleaves).eval(state, tr, va, metric_fn,
                                         **_frozen(base))

    return jax.jit(evaluate)


# eval_points moved to repro.obs.trace (the event log aligns on the same
# grid) and is re-exported here for its original callers.


def assemble_timeline(res: FLResult, profile: str, round_times, drop_t,
                      drop_d, rounds: int, eval_every: int) -> None:
    """Attach a host-side Timeline (and the cumulative simulated time at
    each eval point) to `res` from per-round scan outputs. Shared with
    train.sweep."""
    res.timeline = Timeline(
        profile=profile,
        round_seconds=[float(x) for x in round_times],
        dropped_teams=[int(x) for x in drop_t],
        dropped_devices=[int(x) for x in drop_d])
    res.sim_seconds = res.timeline.at_rounds(
        eval_points(rounds, eval_every))


def run_experiment(algo, params0, train_data, val_data, *,
                   metric_fn: Callable, rounds: int, m: int, n: int,
                   team_frac: float = 1.0, device_frac: float = 1.0,
                   seed: int = 0, eval_every: int = 1, scan: bool = True,
                   system=None, trace=None, trace_dir=None,
                   event_meta: Optional[dict] = None,
                   cohort: Optional[int] = None, base=None) -> FLResult:
    """Drive `algo` for `rounds` global rounds, evaluating every
    `eval_every` rounds (and after the final round). Returns an FLResult
    whose metric histories hold one entry per eval point.

    scan=True compiles the whole experiment into one program (chunked
    lax.scan); scan=False dispatches round-by-round from the host with
    identical semantics — same mask PRNG chain, same eval points.
    system: optional wall-clock model (a `repro.system.SystemSpec`, a
    profile name, or a spec dict): simulate each round's duration and —
    in deadline mode — drop stragglers from the participation masks;
    the result grows a `Timeline` and `sim_seconds` history.
    trace: optional `repro.obs.TraceConfig` (or True for the default
    one): emit per-round probe scalars — and, under ``trace.health``,
    the algorithm's health detectors — as extra scan outputs, assembled
    into ``FLResult.trace`` / ``FLResult.health``; also gates the
    cost-analysis capture, the ``jax.profiler`` context, and
    ``trace.fail_fast`` (raise `repro.obs.health.HealthError` naming
    the first bad round as soon as a dispatched chunk's detectors
    fire). None (default) leaves the compiled program — and the
    trajectory — untouched.
    trace_dir: when set, write the run's JSONL event log (header / eval
    points / footer, `repro.obs.events`) into this directory, plus a
    Chrome-trace span file (`repro.obs.spans`) covering
    build/dispatch/eval — unless a caller already activated a
    `SpanLog`, in which case our spans land there and the caller saves;
    ``event_meta`` is merged into the header (scenario identity etc.).
    cohort: optional cohort width for the virtualized engine (module
    docstring / DESIGN.md §11): only a sampled (M, cohort) slice of the
    population is materialized per round; ``FLResult.cohort_indices``
    records each round's index map and participation/ledger counts
    cover cohort devices only. ``team_frac``/``device_frac`` then
    sample within the cohort.
    base: optional frozen model the tiers are fitted on (per-device LoRA
    adapters over a pretrained base): held once on the device and passed
    to every round and eval as an argument (``core.permfl.permfl_round``);
    ``params0`` is then the initial adapter tree, and ``loss_fn`` /
    ``metric_fn`` take every device at once with the base. Not with
    ``trace`` (its probes evaluate one device at a time).
    """
    if base is not None and trace is not None:
        raise ValueError("trace probes evaluate one device at a time; "
                         "a run over a frozen base takes no trace")
    kw = dict(metric_fn=metric_fn, rounds=rounds, m=m, n=n,
              team_frac=team_frac, device_frac=device_frac, seed=seed,
              eval_every=eval_every, scan=scan, system=system,
              trace=trace, trace_dir=trace_dir, event_meta=event_meta,
              cohort=cohort, base=base)
    install_gc_spans()
    # span-log ownership (repro.obs.spans): the outermost layer with a
    # trace_dir creates, activates, and saves one; when a caller
    # (run_scenario, the scenarios CLI) already activated a log, our
    # spans land there and the caller saves
    if trace_dir is None or current_log() is not None:
        return _run_experiment(algo, params0, train_data, val_data, **kw)
    tag = getattr(algo, "name", None) or "run"
    log = SpanLog(meta={"kind": "experiment", "algo": tag})
    with log.activate():
        try:
            return _run_experiment(algo, params0, train_data, val_data,
                                   **kw)
        finally:
            log.save(trace_dir, tag=tag)


def _run_experiment(algo, params0, train_data, val_data, *, metric_fn,
                    rounds, m, n, team_frac, device_frac, seed,
                    eval_every, scan, system, trace, trace_dir,
                    event_meta, cohort, base) -> FLResult:
    check_participation(algo, team_frac, device_frac)
    if cohort is not None:
        cohort = int(cohort)
        if not 1 <= cohort <= n:
            raise ValueError(
                f"cohort must be in [1, n_devices={n}], got {cohort}")
    if trace is True:
        trace = TraceConfig()
    with span("build", algo=getattr(algo, "name", "?"), m=m, n=n,
              rounds=rounds):
        state = algo.init_state(params0, m, n)
        key = jax.random.PRNGKey(seed)
        n_chunks, rem = divmod(rounds, eval_every)

        sys_key = sleaves = None
        if system is not None:
            system = get_profile(system)
            sys_key = (system.skeleton(), workload_for(algo, params0))
            sleaves, _ = system.tree_floats()

        skel, hleaves = hparam_skeleton(algo)
        kdisp = dispatch_key()
        scanned = _scan_program(skel, metric_fn, m, n, team_frac,
                                device_frac, sys_key, trace, kdisp,
                                cohort)
        eval_jit = _eval_program(skel, metric_fn, kdisp)

    res = FLResult(rounds=rounds, eval_every=eval_every, cohort=cohort,
                   population=n if cohort is not None else None)
    ledger = algo.make_ledger(params0)
    outs_flat = {}          # output name -> flat per-round list
    t0 = time.time()
    t_first = None

    def record(metrics_hist, outs):
        """metrics_hist: dict of (chunks,) arrays; outs: dict of
        (chunks, length) per-round output arrays (cohort_idx rides as
        (chunks, length, M, C) and lands in res.cohort_indices)."""
        for k, v in metrics_hist.items():
            getattr(res, _METRIC_FIELDS[k]).extend(
                float(x) for x in np.asarray(v))
        for k, v in outs.items():
            if k == "cohort_idx":
                arr = np.asarray(v)
                res.cohort_indices.extend(
                    arr.reshape((-1,) + arr.shape[-2:]).astype(int)
                    .tolist())
                continue
            outs_flat.setdefault(k, []).extend(
                np.asarray(v).reshape(-1).tolist())

    fail_ctx = (event_meta or {}).get("scenario") \
        or getattr(algo, "name", None) or "run"

    def check_health():
        """Fail fast on the detector streams accumulated so far —
        outs_flat spans chunks, so indices are global 1-based rounds."""
        if trace is None or not (trace.health and trace.fail_fast):
            return
        HealthReport(series={
            k.split(":", 1)[1]: v for k, v in outs_flat.items()
            if k.startswith("health:")}).check(fail_ctx)

    first_span = None       # the first dispatch's; carries the static cost
    with profile_ctx(trace):
        if scan:
            for length, n_steps in ((eval_every, n_chunks), (rem, 1)):
                if length == 0 or n_steps == 0:
                    continue
                first = t_first is None
                with span("dispatch", chunks=n_steps,
                          rounds_per_chunk=length) as sp:
                    (state, key), (metrics, outs) = scanned(
                        hleaves, state, key, train_data, val_data,
                        sleaves=sleaves, length=length, n_steps=n_steps,
                        **_frozen(base))
                    res.dispatches += 1
                    if first:
                        jax.block_until_ready(state)
                        t_first = time.time()
                        first_span = sp
                with span("eval", chunks=n_steps):
                    record(metrics, outs)
                check_health()
        else:
            if cohort is None:
                round_body = _round_body(algo, m, n, team_frac,
                                         device_frac, sys_key, trace)
                carry, unpack = (state, key), lambda c: c[0]
            else:
                dev, rest, mrg = split_device_state(algo, state, m, n)
                round_body = _round_body(algo, m, n, team_frac,
                                         device_frac, sys_key, trace,
                                         cohort=cohort, merge=mrg)
                carry, unpack = (dev, rest, key), lambda c: mrg(c[0], c[1])
            for t in range(rounds):
                first = t_first is None
                with span("dispatch", round=t + 1) as sp:
                    carry, outs = round_body(carry, None, train_data,
                                             sleaves, base)
                    res.dispatches += 1
                    if first:
                        jax.block_until_ready(carry)
                        t_first = time.time()
                        first_span = sp
                for k, v in outs.items():
                    if k == "cohort_idx":
                        res.cohort_indices.append(
                            np.asarray(v).astype(int).tolist())
                        continue
                    outs_flat.setdefault(k, []).append(
                        float(v) if k == "t_round"
                        or k.startswith(("probe:", "health:")) else int(v))
                check_health()
                if (t + 1) % eval_every == 0 or t == rounds - 1:
                    with span("eval", round=t + 1):
                        metrics = eval_jit(hleaves, unpack(carry),
                                           train_data, val_data,
                                           **_frozen(base))
                        res.dispatches += 1
                        for k, v in metrics.items():
                            getattr(res, _METRIC_FIELDS[k]).append(
                                float(v))
            state, key = unpack(carry), carry[-1]

    t_end = time.time()
    res.compile_seconds = (t_first if t_first is not None else t_end) - t0
    res.run_seconds = t_end - (t_first if t_first is not None else t_end)
    res.seconds = res.compile_seconds + res.run_seconds
    res.state = state

    probe_series = {k.split(":", 1)[1]: outs_flat.pop(k)
                    for k in sorted(outs_flat) if k.startswith("probe:")}
    health_series = {k.split(":", 1)[1]: outs_flat.pop(k)
                     for k in sorted(outs_flat)
                     if k.startswith("health:")}
    if trace is not None:
        cost = None
        if trace.cost_analysis and scan and n_chunks:
            # shapes are all that matter; the live operands carry them
            cost = compiled_cost(scanned, hleaves, state, key, train_data,
                                 val_data, sleaves=sleaves,
                                 length=eval_every, n_steps=n_chunks)
            if cost and first_span is not None:
                # late-stamp the static cost next to the first dispatch's
                # time — Span.set works after close, the log saves later
                first_span.set(**cost)
        res.trace = RunTrace(config=trace, series=probe_series, cost=cost)
        if trace.health:
            res.health = HealthReport(series=health_series)

    res.participation = list(zip(
        [int(x) for x in outs_flat.get("teams", [])],
        [int(x) for x in outs_flat.get("devices", [])]))
    if system is not None:
        assemble_timeline(res, system.name, outs_flat["t_round"],
                          outs_flat["dropped_teams"],
                          outs_flat["dropped_devices"], rounds, eval_every)

    if ledger is not None:
        for n_teams, n_devices in res.participation:
            algo.log_comm_round(ledger, n_teams=n_teams, n_devices=n_devices)
        res.comm = ledger

    if trace_dir is not None:
        res.events_path = str(write_run(
            trace_dir, res, algo=algo,
            meta={"m": m, "n": n, "seed": seed, "team_frac": team_frac,
                  "device_frac": device_frac, "scan": scan,
                  "system": system.name if system is not None else None,
                  **({"cohort": cohort} if cohort is not None else {}),
                  **(event_meta or {})}))
    return res
