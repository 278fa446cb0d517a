"""Driver of the training cells: ``repro.train.engine.run_experiment``
with ``scan=True``, called back to back. Each call is one whole
experiment of the cell's rounds and evals, from the initial model, on
data and weights already on the device; it ends with its state ready.

With ``cohort`` set, the population lives in the engine's device-state
store and each round gathers a sampled cohort; the reference then
replays the rounds along the cohort indices the call reports."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, fl, permfl_ref
from chipbench.data import int_seed, seed_key
from chipbench.harness import Run


def setup(ctx):
    """Data, weights, the program's algorithm and the call the window
    repeats."""
    from repro.core import PerMFL
    from repro.core.permfl import PerMFLHParams
    from repro.scenarios.spec import fns_for
    from repro.train.engine import run_experiment

    p, cfg = ctx.params, ctx.cell.config
    k_data, k_init, k_run = jax.random.split(seed_key(ctx.seed), 3)
    train, val = fl.federation(k_data, p)
    params0 = fl.init_fn(ctx.cell.reference, cfg)(k_init)
    jax.block_until_ready((train, val, params0))
    ctx.mark("data and weights made")
    loss_fn, metric_fn = fns_for(fl.program_config(cfg))
    hp = dict(p["hp"])
    algo = PerMFL(loss_fn, PerMFLHParams(**hp))
    engine_seed = int_seed(k_run)

    def call():
        return run_experiment(
            algo, params0, train, val, metric_fn=metric_fn,
            rounds=p["rounds"], m=p["m"], n=p["n"],
            eval_every=p["eval_every"], seed=engine_seed, scan=True,
            cohort=p.get("cohort"))

    return train, params0, call


def program_outputs(res, params0, cohort: bool) -> dict:
    """What the comparison reads of a call's result, taken before the
    program's state is freed."""
    st = res.state
    out = {"losses": list(res.train_loss),
           "norms": compare.change_norms(st.x, st.w, st.theta, params0)}
    if cohort:
        idx = np.asarray(res.cohort_indices)           # (rounds, M, C)

        @jax.jit
        def moved(theta):
            rows = [jnp.any(a != p, axis=tuple(range(2, a.ndim)))
                    for a, p in zip(jax.tree.leaves(theta),
                                    jax.tree.leaves(params0))]
            return jnp.any(jnp.stack(rows), axis=0)

        out["moved"] = np.asarray(moved(st.theta))     # (M, N) bool
        out["indices"] = idx
        ti, di = sampled_rows(idx, st.theta)
        out["rows"] = flat_rows(jax.tree.map(lambda a: a[ti, di], st.theta))
    return out


def _flat_keys(indices, n):
    """team * n + device of every (round, team, slot) of the cohorts."""
    teams = np.arange(indices.shape[1])[None, :, None]
    return (teams * n + indices).reshape(-1)


def sampled_rows(indices, theta):
    """(teams, devices) of the rows the call's cohorts touched, sorted
    by (team, device); ``theta`` gives the population N."""
    n = jax.tree.leaves(theta)[0].shape[1]
    keys = np.unique(_flat_keys(indices, n))
    return keys // n, keys % n


def flat_rows(tree) -> np.ndarray:
    """(R, ...) leaves -> (R, features) on the host."""
    return np.concatenate([np.asarray(a, np.float64).reshape(a.shape[0], -1)
                           for a in jax.tree.leaves(tree)], axis=1)


def reference(ctx, train, params0, indices=None, *, dtype=jnp.float32,
              half_batch=False):
    """The plain reference of the same experiment: (losses, norms[,
    the set of rows it moved])."""
    p, cfg = ctx.params, ctx.cell.config
    loss = fl.ref_loss(ctx.cell.reference, cfg)
    hp = fl.hparams(p)
    static = dict(loss=loss, k_team=p["hp"]["k_team"],
                  l_local=p["hp"]["l_local"])
    if indices is None:
        losses, (x, w, theta) = permfl_ref.run(
            params0, train, hp, m=p["m"], n=p["n"], rounds=p["rounds"],
            eval_every=p["eval_every"], dtype=dtype, half_batch=half_batch,
            **static)
        return (np.asarray(losses),
                compare.change_norms(x, w, theta, params0), None)
    return cohort_reference(ctx, train, params0, indices, hp, static,
                            dtype=dtype, half_batch=half_batch)


def cohort_reference(ctx, train, params0, indices, hp, static, *, dtype,
                     half_batch):
    """Replay the rounds along the call's cohort indices. Only sampled
    rows ever hold other than the initial model, so the reference keeps
    those rows alone and evaluates the rest of the population at the
    initial model."""
    p = ctx.params
    m, c = indices.shape[1:]
    take = jax.jit(lambda tree, i: jax.tree.map(
        lambda a: jax.vmap(lambda row, j: row[j])(a, i), tree))
    x = params0
    rows = []
    for r in range(indices.shape[0]):
        idx = jnp.asarray(indices[r])
        x, w, theta = permfl_ref.cohort_round(
            x, take(train, idx), hp, m=m, c=c, dtype=dtype,
            half_batch=half_batch, **static)
        rows.append(theta)
    # each sampled row ends as its last round left it: of its
    # occurrences in (round, team, slot) order, the last one
    flat = _flat_keys(indices, p["n"])
    keys, last = np.unique(flat[::-1], return_index=True)
    pick = flat.size - 1 - last
    r_, t_, j_ = np.unravel_index(pick, indices.shape)
    stacked = jax.tree.map(lambda *a: jnp.stack(a)[r_, t_, j_], *rows)
    loss = static["loss"]
    per_device = jax.jit(jax.vmap(jax.vmap(loss, in_axes=(None, 0)),
                                  in_axes=(None, 0)))
    base = np.asarray(per_device(params0, train), np.float64)   # (M, N)
    ti, di = keys // p["n"], keys % p["n"]
    own = jax.tree.map(lambda a: a[ti, di], train)
    moved_loss = np.asarray(jax.jit(jax.vmap(loss))(stacked, own),
                            np.float64)
    total = base.sum() - base[ti, di].sum() + moved_loss.sum()
    losses = np.asarray([total / base.size])
    norms = compare.change_norms(
        x, w, jax.tree.map(lambda a: a[None], stacked), params0)
    moved = np.zeros((m, p["n"]), bool)
    moved[ti, di] = True
    base_rows = flat_rows(jax.tree.map(
        lambda a: jnp.broadcast_to(a, (len(keys),) + a.shape), params0))
    return losses, norms, (moved, flat_rows(stacked), base_rows)


def numbers(prog: dict, ref) -> dict:
    """loss_gap and change_gap; for a cohort run also rows_mismatch (rows the program moved that the reference did not, or
    the other way) and
    row_gap: over the sampled rows, the largest distance between the
    program's row and the reference's, over the reference's change of
    that row. MCLR is convex, so rows agree element by element."""
    losses, norms, rows = ref
    out = {"loss_gap": compare.loss_gap(prog["losses"], losses),
           "change_gap": compare.change_gap(prog["norms"], norms)}
    if rows is not None:
        moved, ref_rows, base_rows = rows
        out["rows_mismatch"] = int(np.sum(prog["moved"] != moved))
        diff = np.linalg.norm(prog["rows"] - ref_rows, axis=1)
        step = np.linalg.norm(ref_rows - base_rows, axis=1)
        out["row_gap"] = float(np.max(diff / step)) \
            if np.all(np.isfinite(diff)) else float("inf")
    return out


def run(ctx) -> Run:
    p = ctx.params
    cohort = p.get("cohort") is not None
    train, params0, call = setup(ctx)
    res = call()                                  # compiles every shape
    jax.block_until_ready(res.state)
    res = None
    ctx.mark("first call done")
    calls = 0
    with ctx.window():
        setup_s = ctx.since_start()
        start = time.perf_counter()
        while True:
            with ctx.annotate("call"):
                res = None                        # free the last state
                res = call()
                jax.block_until_ready(res.state)
            calls += 1
            if time.perf_counter() - start >= ctx.seconds:
                break
        window_s = time.perf_counter() - start
    memory = ctx.memory_peak()
    ctx.mark("window closed")
    prog = program_outputs(res, params0, cohort)
    del res
    ref = reference(ctx, train, params0, prog.get("indices"))
    ctx.mark("reference done")
    samples = fl.samples_per_call(p) * calls
    return Run(setup_s=setup_s, window_s=window_s, attempted=calls,
               failed=0,
               end_to_end={p["rate_metric"]: samples / window_s},
               stats={"calls": calls, "samples": samples,
                      "samples_per_s": samples / window_s},
               numbers=numbers(prog, ref), memory_peak_bytes=memory)
