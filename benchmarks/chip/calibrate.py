"""Readings the limits of ``correct`` are set from, on the chip at the
cell's own size: for each seed, the program's numbers (set-up and one
call of the timed entry, or a short window for a serving cell), the
control's (the plain reference one precision step down, put in the
program's place) and, for training cells, the planted fault of half of
each device's batch left out. Not part of a benchmark run.

    python3 benchmarks/chip/calibrate.py --workload paper-cnn.train \\
        --seeds 1,2,3 --controls 3 --seconds 3

Prints one JSON line per seed and what it read."""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def readings(cell, driver, ctx, controls: bool, look: bool = False) -> dict:
    """The program's numbers for ``ctx.seed`` and, with ``controls``,
    the control's and (training cells) the half-batch fault's. ``look``
    adds, for a training cell, each leaf's gap and the program's numbers
    with its matmuls at HIGHEST precision (same arithmetic, less
    rounding)."""
    import jax
    import jax.numpy as jnp
    line = {}
    kind = cell.params["driver"]
    if kind == "experiment":
        from chipbench import compare
        cohort = cell.params.get("cohort") is not None
        train, params0, call = driver.setup(ctx)
        res = call()
        jax.block_until_ready(res.state)
        prog = driver.program_outputs(res, params0, cohort)
        del res
        ref = driver.reference(ctx, train, params0, prog.get("indices"))
        line["program"] = driver.numbers(prog, ref)
        if look:
            line["program_leaves"] = compare.leaf_gaps(prog["norms"], ref[1])
            line["program_losses"] = [[float(v) for v in prog["losses"]],
                                      [float(v) for v in ref[0]]]
            with jax.default_matmul_precision("highest"):
                res = call()
                jax.block_until_ready(res.state)
            high = driver.program_outputs(res, params0, cohort)
            del res
            line["program_highest"] = driver.numbers(high, ref)
            line["program_highest_leaves"] = compare.leaf_gaps(
                high["norms"], ref[1])
        if controls:
            for name, kw in (("control", {"dtype": jnp.bfloat16}),
                             ("half_batch", {"half_batch": True})):
                losses, norms, rows = driver.reference(
                    ctx, train, params0, prog.get("indices"), **kw)
                stand_in = {"losses": losses, "norms": norms}
                if rows is not None:
                    stand_in.update(moved=rows[0], rows=rows[1])
                line[name] = driver.numbers(stand_in, ref)
    else:
        import numpy as np

        from chipbench import compare, controls as low_precision, traffic
        p = cell.params
        x, w, k_d, server, pool = driver.setup(ctx)
        due, teams, devs, images = driver.schedule(ctx, ctx.seconds)
        sent, done, window_s, batches, _ = driver.serve_window(
            server, pool, due, teams, devs, images,
            tuple(p["batch_sizes"]), ctx.annotate)
        rows = np.sort(traffic.rng_for(ctx.seed, 4).choice(
            len(due), size=min(p["sample"], len(due)), replace=False))
        served = driver.served_rows(batches, rows)
        del batches, server
        args = (ctx, x, w, k_d, teams[rows], devs[rows],
                pool[images[rows]])
        ref = driver.reference_logits(*args)
        line["program"] = {"logit_gap": compare.logit_gap(served, ref)}
        line["requests"] = len(due)
        if controls:
            low = driver.reference_logits(
                *args, quantize=lambda a: low_precision.quantize_rows(a, 4))
            line["control"] = {"logit_gap": compare.logit_gap(low, ref)}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds for the program's readings")
    ap.add_argument("--controls", type=int, default=3,
                    help="how many of those seeds also read the control "
                         "and the faults")
    ap.add_argument("--seconds", type=float, default=3.0,
                    help="window of a serving cell's short run")
    ap.add_argument("--out", default=None, help="also append lines here")
    ap.add_argument("--look", action="store_true",
                    help="training cells: per-leaf gaps, and the program "
                         "again at HIGHEST matmul precision")
    args = ap.parse_args(argv)

    from chipbench import harness
    cell = harness.find_cell(args.workload)
    harness.program_path()
    harness.compile_cache()
    devices, peak = harness.chips_for(cell)
    driver = harness.load_module(
        harness.BENCH_DIR / "drivers" / f"{cell.params['driver']}.py",
        "chipbench_driver_" + cell.params["driver"])
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        ctx = harness.Context(cell, seed, args.seconds, False, devices, peak,
                              t)
        line = {"workload": args.workload, "seed": seed}
        line.update(readings(cell, driver, ctx, i < args.controls,
                             args.look))
        line["seconds"] = time.perf_counter() - t
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
