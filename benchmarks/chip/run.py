#!/usr/bin/env python3
"""The chip benchmark's command: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for. It exits non-zero, printing no result, when JAX finds no TPU or too
few chips, or when the program is not in the checkout. The last line of
standard output is the result; the compared numbers and their limits are
also the last lines of standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"the program under test (src/repro) is not in {ROOT}")
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import harness

    cell = harness.load_cell(args.workload)
    devices = harness.check_device(cell.chips)
    metrics = harness.metric_names(args.workload, bool(args.trace))
    return harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            metrics=metrics, devices=devices, t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
