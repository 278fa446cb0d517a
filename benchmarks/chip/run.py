"""The chip benchmark: one cell, one seed, one measured window.

    python3 benchmarks/chip/run.py --workload paper-cnn.train \\
        --seed 12345 --seconds 20 --trace 0

Sets the cell up from the seed (data and weights made on the device, every
shape the window uses compiled or loaded from the compile cache), drives
the program's own entry for ``--seconds``, checks what the timed path
produced against the plain reference, and prints the result as the last
line of standard output: the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics read from a device trace of the
window. The numbers compared, each beside its limit, are the last lines
of standard error. Exits non-zero, printing no result, without the chips
the cell asks for.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    from chipbench.harness import run_cell
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t0=T0)
    for name, value in result.pop("uncompared").items():
        print(f"not compared: {name} = {value!r}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
