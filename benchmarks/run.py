"""Benchmark harness — one module per paper table/figure + system benches.

    PYTHONPATH=src python -m benchmarks.run            # quick (CPU-sized)
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale rounds
    PYTHONPATH=src python -m benchmarks.run --only table1,fig3

Output is CSV-ish lines `table,key...,value` plus `#` commentary; each
module returns a list of failed qualitative reproduction checks, and the
process exits non-zero if any check failed.
"""
from __future__ import annotations

import argparse
import sys
import time

MODULES = [
    ("table1", "benchmarks.table1"),            # Table 1 performance
    ("fig2", "benchmarks.fig2_convergence"),    # Fig 2 convergence
    ("fig3", "benchmarks.fig3_hparams"),        # Fig 3 hyperparameters
    ("table2", "benchmarks.table2_teams"),      # Table 2 team formation
    ("fig4", "benchmarks.fig4_participation"),  # Fig 4 participation
    ("fig_comm", "benchmarks.fig_comm_tradeoff"),  # acc-vs-MB comm sweep
    ("fig_tta", "benchmarks.fig_time_to_accuracy"),  # acc-vs-sim-seconds
    ("engine", "benchmarks.bench_engine"),      # scan vs dispatch rounds/s
    ("theory", "benchmarks.theory_rates"),      # Thm 1/2 rate validation
    ("roofline", "benchmarks.roofline_table"),  # §Roofline from dry-run
    ("kernels", "benchmarks.bench_kernels"),    # kernel micro-bench
    ("serving", "benchmarks.bench_serving"),    # serve engine throughput
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale round counts (slow)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: "
                    + ",".join(k for k, _ in MODULES))
    args = ap.parse_args(argv)
    subset = set(args.only.split(",")) if args.only else None

    import importlib
    failures = []
    t_start = time.time()
    for key, modname in MODULES:
        if subset and key not in subset:
            continue
        print(f"\n### {key} ({modname}) " + "#" * 40)
        t0 = time.time()
        mod = importlib.import_module(modname)
        try:
            fails = mod.main(quick=not args.full) or []
        except Exception as e:  # noqa: BLE001 — report, keep going
            import traceback
            traceback.print_exc()
            fails = [f"crashed: {e!r}"]
        failures.extend(f"{key}: {f}" for f in fails)
        print(f"### {key} done in {time.time() - t0:.0f}s")

    print(f"\n=== benchmarks finished in {time.time() - t_start:.0f}s ===")
    if failures:
        print("QUALITATIVE CHECK FAILURES:")
        for f in failures:
            print("  -", f)
        return 1
    print("all qualitative reproduction checks passed")
    return 0


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    sys.exit(main())
