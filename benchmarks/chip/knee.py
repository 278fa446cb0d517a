"""Find the knee of a serving cell once, on the chip: serve the cell's
traffic at each of a list of offered rates, in one process over one
set-up, and report for each whether the queue grew over the window.

    python3 benchmarks/chip/knee.py --workload paper-cnn.serve \\
        --rates 4000,8000,16000 --seconds 5 --seed 7

The knee is the highest rate whose queue does not grow: the requests of
the window's last quarter wait no longer than those of its first, and
requests are completed at the offered rate. The cell's file then takes
0.8 x the knee. Prints one JSON line per rate."""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    import time

    import numpy as np

    from chipbench import harness, traffic
    from chipbench.stats import percentile
    cell = harness.find_cell(args.workload)
    harness.program_path()
    harness.compile_cache()
    devices, peak = harness.chips_for(cell)
    driver = harness.load_module(harness.BENCH_DIR / "drivers" / "serve.py",
                                 "chipbench_driver_serve")
    ctx = harness.Context(cell, args.seed, args.seconds, False, devices,
                          peak, time.perf_counter())
    x, w, k_d, server, pool = driver.setup(ctx)
    sizes = tuple(cell.params["batch_sizes"])
    for rate in (float(r) for r in args.rates.split(",")):
        due, teams, devs, images = driver.schedule(ctx, args.seconds, rate)
        sent, done, window_s, batches, _ = driver.serve_window(
            server, pool, due, teams, devs, images, sizes, ctx.annotate)
        lat = traffic.latency_ms(due, done)
        q = len(due) // 4
        first, last = np.median(lat[:q]), np.median(lat[-q:])
        rps = len(due) / window_s
        print(json.dumps({
            "rate": rate, "requests": len(due), "served_rps": rps,
            "p50_ms": percentile(lat, 50), "p99_ms": percentile(lat, 99),
            "late_p99_ms": percentile(traffic.lateness_ms(due, sent), 99),
            "first_quarter_p50_ms": first, "last_quarter_p50_ms": last,
            "mean_batch": len(due) / len(batches),
            "grows": bool(last > 2 * first + 5 or rps < 0.98 * rate)}),
            flush=True)
        del batches
    return 0


if __name__ == "__main__":
    sys.exit(main())
