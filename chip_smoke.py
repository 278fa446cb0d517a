"""Drive train -> export -> serve once on a TPU and check what comes out.

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --four-chips   # four chips: the sharded sweep only

One process, phases in order, one line each:

  device  the platform must be ``tpu`` and kernels must resolve to
          compiled Pallas; anything else exits non-zero at once.
  train   ``table1/mnist/cnn/permfl`` (paper CNN at its published
          widths, registered 4 x 10 topology) for 3 rounds through
          ``run_scenario`` with fail-fast health monitors; then one round
          under Pallas and one under ``REPRO_KERNEL_MODE=xla``, both
          with f32 matmuls, whose states must agree within
          ``PALLAS_VS_XLA_RTOL``.
  comm    the fused top-k / rand-k / int8 / sign uplinks, 2 rounds each.
  cohort  ``cohort/virtual/n100000``: the (M, N) device-state store
          resident on the chip, 2 rounds.
  serve   the CNN run exported to an int8 and a delta ``ModelStore``,
          saved and reloaded, then 512 Zipf requests (10% unknown
          principals) replayed through ``PersonalizedServer``.
  sweep   (``--four-chips`` only) ``fig3/mnist/mclr`` over 2 beta values
          x 2 seeds, sharded over a 4-chip sweep mesh and unsharded; the
          per-config results must agree.

A failed phase prints its traceback and the run goes on to the next
phase, but then exits non-zero. Only a run in which every phase passed
prints the last line ``{"ok": true, "device": {...}}``. Latencies it
prints are host-clock information, not metrics.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import tempfile
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

CNN = "table1/mnist/cnn/permfl"
COMM = tuple(f"comm/mnist/mclr/{c}"
             for c in ("topk_10", "randk_10", "int8", "sign"))
COHORT = "cohort/virtual/n100000"
SWEEP = "fig3/mnist/mclr"

# One PerMFL round (K=10 x L=20 prox steps) under compiled Pallas
# against the same round under the XLA references. Both run with f32
# ("highest") matmuls: at the TPU's default precision a matmul rounds
# its operands to bf16, so the one-ulp difference a single prox step
# leaves (6e-8 on a v5e) flips roundings and grows over the round's 200
# steps to half a leaf's scale. With f32 matmuls the round stays within
# 1e-7 of each state leaf's largest magnitude on a v5e; the limit
# leaves a factor of 100.
PALLAS_VS_XLA_RTOL = 1e-5
# served logits against a direct forward of the same params: the
# batched, vmapped program and a batch-of-one forward accumulate in a
# different order (1.4e-6 apart on a v5e)
SERVE_RTOL = SERVE_ATOL = 1e-4
# the sweep sharded over four chips against the same sweep on one: the
# same per-config program, partitioned, so states agree to f32 rounding
# and accuracies to within a couple of flipped validation predictions
SHARDED_STATE_RTOL = 1e-4
SHARDED_ACC_ATOL = 5e-3


def check(ok: bool, what: str) -> None:
    """Raise unless ``ok``: the smoke's checks survive ``python -O``."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


@contextlib.contextmanager
def env(name: str, value: str):
    """Set one environment variable for the block, then restore it."""
    old = os.environ.get(name)
    os.environ[name] = value
    try:
        yield
    finally:
        if old is None:
            del os.environ[name]
        else:
            os.environ[name] = old


def leaf_routing(params) -> str:
    """How many leaves the compress ops send to Pallas and to XLA
    (``ops.resolve_leaf_mode`` routes leaves above the VMEM bound)."""
    import jax

    from repro.kernels.compress.ops import resolve_leaf_mode
    from repro.kernels.interface import KernelType, kernel_mode

    kinds = [resolve_leaf_mode(kernel_mode(), leaf.size)
             for leaf in jax.tree.leaves(params)]
    n_xla = sum(k is KernelType.XLA for k in kinds)
    return f"compress leaves: {len(kinds) - n_xla} pallas, {n_xla} xla"


def checked_run(name: str, rounds: int):
    """``run_scenario`` with fail-fast health; the result must be
    healthy and its last train loss finite."""
    from repro.obs import TraceConfig
    from repro.scenarios import run_scenario

    res = run_scenario(name, rounds=rounds,
                       trace=TraceConfig(fail_fast=True))
    loss = res.train_loss[-1]
    check(math.isfinite(loss), f"{name}: train loss {loss} is finite")
    check(res.health is not None and res.health.ok,
          f"{name}: health monitors report ok")
    return res


def describe(res) -> str:
    accs = " ".join(f"{k}={getattr(res, k + '_acc')[-1]:.4f}"
                    for k in ("pm", "tm", "gm") if getattr(res, k + "_acc"))
    return (f"train_loss={res.train_loss[-1]:.6g} {accs} "
            f"compile={res.compile_seconds:.1f}s run={res.run_seconds:.2f}s")


def max_state_diff(a, b) -> tuple:
    """(largest |a - b|, largest |a - b| / max|b|) over float leaves."""
    import jax
    import numpy as np

    worst_abs = worst_rel = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        if not np.issubdtype(y.dtype, np.floating) or y.size == 0:
            continue
        d = float(np.max(np.abs(x - y)))
        worst_abs = max(worst_abs, d)
        worst_rel = max(worst_rel, d / max(float(np.max(np.abs(y))), 1e-30))
    return worst_abs, worst_rel


# ------------------------------------------------------------- phases

def phase_device(expect: int) -> dict:
    import jax

    from repro.kernels.interface import KernelType, kernel_mode

    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)} jax={jax.__version__}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {d.platform!r})")
    if len(devs) < expect:
        raise SystemExit(f"chip_smoke: needs {expect} chips, "
                         f"JAX found {len(devs)}")
    kt = kernel_mode()
    print(f"device: kernel_mode={kt.value}", flush=True)
    if kt is not KernelType.PALLAS:
        raise SystemExit(f"chip_smoke: kernels resolve to {kt.value}, "
                         "not compiled pallas")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def phase_train(ctx: dict) -> None:
    import jax

    from repro.kernels.interface import KernelType, kernel_mode
    from repro.scenarios import build_scenario

    mode = kernel_mode()
    b = build_scenario(CNN)
    res = checked_run(CNN, 3)
    ctx["cnn"] = (b, res)
    print(f"train: {CNN} {b.m}x{b.n} rounds=3 kernel_mode={mode.value} "
          f"{describe(res)} health=ok; {leaf_routing(b.params0)}",
          flush=True)

    with jax.default_matmul_precision("highest"):
        one_kernel = checked_run(CNN, 1)
        with env("REPRO_KERNEL_MODE", "xla"):
            check(kernel_mode() is KernelType.XLA, "REPRO_KERNEL_MODE=xla")
            one_xla = checked_run(CNN, 1)
    check(kernel_mode() is mode, f"kernel mode back to {mode.value}")
    d_abs, d_rel = max_state_diff(one_kernel.state, one_xla.state)
    print(f"train: 1 round {mode.value} vs xla (f32 matmuls): "
          f"max|diff|={d_abs:.3e} "
          f"max|diff|/max|x|={d_rel:.3e} (limit {PALLAS_VS_XLA_RTOL:g})",
          flush=True)
    check(d_rel <= PALLAS_VS_XLA_RTOL,
          f"{mode.value} and xla states agree within "
          f"{PALLAS_VS_XLA_RTOL:g}")


def phase_comm(ctx: dict) -> None:
    from repro.kernels.interface import compress_fused, kernel_mode
    from repro.scenarios import build_scenario

    check(compress_fused(), "fused compress kernels selected")
    for name in COMM:
        res = checked_run(name, 2)
        tot = res.comm.totals()
        print(f"comm: {name} rounds=2 kernel_mode={kernel_mode().value} "
              f"{describe(res)} uplink+downlink={tot.total / 1e6:.3f}MB; "
              f"{leaf_routing(build_scenario(name).params0)}", flush=True)


def phase_cohort(ctx: dict) -> None:
    import jax

    from repro.kernels.interface import kernel_mode

    res = checked_run(COHORT, 2)
    big = max(jax.tree.leaves(res.state), key=lambda x: x.size)
    platforms = {d.platform for d in big.devices()}
    check(platforms == {jax.devices()[0].platform},
          f"population state on the accelerator: {platforms}")
    print(f"cohort: {COHORT} rounds=2 kernel_mode={kernel_mode().value} "
          f"population={res.population}/team cohort={res.cohort} "
          f"largest state leaf {tuple(big.shape)} on {sorted(platforms)} "
          f"{describe(res)}", flush=True)


def phase_serve(ctx: dict) -> None:
    import jax
    import numpy as np

    from repro.models import paper_models as pm
    from repro.serve import ModelStore, PersonalizedServer, replay_traffic
    from repro.serve.personalized import zipf_requests

    check("cnn" in ctx, "the train phase produced a CNN run to export")
    b, res = ctx["cnn"]
    cfg = b.config
    xv = np.asarray(b.val["x"], np.float32)
    pool = xv.reshape((-1,) + xv.shape[3:])

    def apply1(p, x):
        return pm.apply(p, cfg, x[None])[0]

    for encoding in ("int8", "delta"):
        store = ModelStore.from_result(b.algo, res, m=b.m, n=b.n,
                                       encoding=encoding)
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "store.ckpt")
            store.save(path)
            store = ModelStore.load(path)
        server = PersonalizedServer(store, apply1)
        stats = replay_traffic(server, pool, requests=512, batch=64,
                               unknown_frac=0.1, seed=0)
        tiers = stats["tier_counts"]
        check(sum(tiers.values()) == stats["requests"],
              f"{encoding}: tier counts {tiers} sum to "
              f"{stats['requests']} requests")
        check(tiers["team"] + tiers["global"] > 0,
              f"{encoding}: unknown principals fell back ({tiers})")

        ts, ds = zipf_requests(b.m, b.n, 8, unknown_frac=0.1, seed=1)
        xs = pool[:8]
        served = np.asarray(server.serve(ts, ds, xs))
        ref = np.stack([np.asarray(apply1(store.params_for(t, d), x))
                        for t, d, x in zip(ts, ds, xs)])
        diff = float(np.max(np.abs(served - ref)))
        check(np.allclose(served, ref, rtol=SERVE_RTOL, atol=SERVE_ATOL),
              f"{encoding}: served logits match a direct forward "
              f"(max|diff|={diff:.3e})")
        if encoding == "delta":
            # the exact encoding must hand back the trained models
            for t, d in zip(ts, ds):
                if t < b.m and d < b.n:
                    got = store.params_for(int(t), int(d))
                    want = b.algo.serving_params(res.state, int(t), int(d))
                    for g, w in zip(jax.tree.leaves(got),
                                    jax.tree.leaves(want)):
                        check(np.array_equal(np.asarray(g), np.asarray(w)),
                              f"delta store returns trained params ({t},{d})")
        print(f"serve: {encoding} store {b.m}x{b.n} saved+reloaded, "
              f"device tier {stats['device_tier_bytes'] / 1e6:.3f}MB; "
              f"{stats['requests']} requests tiers={tiers}; 8 logits vs "
              f"direct forward max|diff|={diff:.3e}", flush=True)
        print(f"info: serve {encoding} host clock qps={stats['qps']:.1f} "
              f"p50={stats['p50_ms']:.3f}ms p99={stats['p99_ms']:.3f}ms",
              flush=True)


def phase_sweep(ctx: dict) -> None:
    import jax
    import numpy as np

    from repro.launch.mesh import make_sweep_mesh
    from repro.scenarios import sweep_scenario

    grid = [{"beta": 0.2}, {"beta": 0.6}]
    seeds = (0, 1)
    mesh = make_sweep_mesh(4, n_data=1, n_model=1)
    sharded = sweep_scenario(SWEEP, grid=grid, seeds=seeds, rounds=5,
                             mesh=mesh)
    plain = sweep_scenario(SWEEP, grid=grid, seeds=seeds, rounds=5)
    big = max(jax.tree.leaves(sharded.state_stacked), key=lambda x: x.size)
    n_dev = len(big.sharding.device_set)
    check(n_dev == 4, f"swept state spans 4 devices, not {n_dev}")
    worst = 0.0
    for a, p in zip(sharded, plain):
        for k in ("pm_acc", "tm_acc", "gm_acc", "train_loss"):
            x, y = np.asarray(getattr(a, k)), np.asarray(getattr(p, k))
            check(np.allclose(x, y, rtol=SHARDED_STATE_RTOL,
                              atol=SHARDED_ACC_ATOL),
                  f"sweep {k}: sharded {x} vs unsharded {y}")
            worst = max(worst, float(np.max(np.abs(x - y))))
    d_abs, d_rel = max_state_diff(
        jax.device_get(sharded.state_stacked),
        jax.device_get(plain.state_stacked))
    check(d_rel <= SHARDED_STATE_RTOL,
          f"sharded and unsharded states agree (rel {d_rel:.3e})")
    print(f"sweep: {SWEEP} {len(sharded)} configs (beta x seed) rounds=5 "
          f"on mesh {dict(mesh.shape)}: state {tuple(big.shape)} on "
          f"{n_dev} devices; metrics max|diff|={worst:.3e}, state "
          f"max|diff|={d_abs:.3e} rel={d_rel:.3e} vs one-chip sweep",
          flush=True)


def run_phases(phases, ctx: dict) -> list:
    """Run each (name, fn) in order; return the names that failed."""
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn(ctx)
        except Exception:  # noqa: BLE001 — report, run the rest, exit 1
            traceback.print_exc()
            failed.append(name)
            print(f"[{name}] FAILED ({time.perf_counter() - t0:.1f}s)",
                  flush=True)
        else:
            print(f"[{name}] ok ({time.perf_counter() - t0:.1f}s)",
                  flush=True)
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sweep sharded over four chips, "
                         "against the same sweep unsharded")
    args = ap.parse_args(argv)

    from repro.launch.cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    device = phase_device(4 if args.four_chips else 1)
    if args.four_chips:
        phases = [("sweep", phase_sweep)]
    else:
        phases = [("train", phase_train), ("comm", phase_comm),
                  ("cohort", phase_cohort), ("serve", phase_serve)]
    failed = run_phases(phases, {})
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
