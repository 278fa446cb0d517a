"""Readings the limits of ``correct`` are set from, for a cell whose
driver defines ``readings(ctx, controls)`` (the LoRA and sweep drivers),
on the chips at the cell's own size: for each seed the program's
numbers and, for the first ``--controls`` seeds, the control's (the
plain reference one precision step down, put in the program's place)
and the planted fault of half of each device's batch left out. Not part
of a benchmark run.

    python3 benchmarks/chip/calibrate_cell.py \\
        --workload deepseek-v2-lite.lora --seeds 1,2,3 --controls 2

Prints one JSON line per seed."""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds for the program's readings")
    ap.add_argument("--controls", type=int, default=2,
                    help="how many of those seeds also read the control "
                         "and the fault")
    ap.add_argument("--out", default=None, help="also append lines here")
    args = ap.parse_args(argv)

    from chipbench import harness
    cell = harness.find_cell(args.workload)
    harness.program_path()
    harness.compile_cache()
    devices, peak = harness.chips_for(cell)
    driver = harness.load_module(
        harness.BENCH_DIR / "drivers" / f"{cell.params['driver']}.py",
        "chipbench_driver_" + cell.params["driver"])
    if not hasattr(driver, "readings"):
        raise harness.Refused(f"driver {cell.params['driver']!r} takes "
                              "calibrate.py")
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        ctx = harness.Context(cell, seed, 0.0, False, devices, peak, t)
        line = {"workload": args.workload, "seed": seed}
        line.update(driver.readings(ctx, i < args.controls))
        line["seconds"] = time.perf_counter() - t
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
