#!/usr/bin/env python3
"""Readings the correctness limit is set from, many seeds in one process.

    python3 benchmarks/chip/calibrate.py --workload granite-20b.chat \\
        --seeds 11,12,13 --seconds 50 [--controls fp8,int8] [--rate 2.4]

For each seed it serves the cell as a benchmark run does (weights and
traffic from the seed, every tail length warmed, traffic before the window,
a window of `--seconds` at the cell's rate or at `--rate`), then compares
the sampled served tokens with the float32 reference. It prints one JSON
line per seed: the program's widest logit gap and the `correct` a run would
print with it, and for each control (the reference with its matrices
rounded to float8 e4m3, or to int8 per output channel) the widest gap of
the token the control puts first at the same positions, judged by the same
decision. The limit lies between the largest program reading and the
smallest control reading.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--rate", type=float, default=None,
                    help="requests per second (default: the cell's)")
    ap.add_argument("--controls", default="fp8,int8")
    args = ap.parse_args(argv)
    import harness

    cell = harness.load_cell(args.workload)
    if args.rate is not None:
        cell.serve["rate"] = args.rate
    devices = harness.check_device(cell.chips)
    controls = tuple(c for c in args.controls.split(",") if c)
    for seed in (int(x) for x in args.seeds.split(",")):
        t0 = time.perf_counter()
        served = harness.serve_cell(cell, seed, args.seconds, False, devices=devices,
                                    t_start=t0)
        run = served.run
        picked = harness.sample(served.finished, run.t_open, run.t_close, seed,
                                cell.serve["sample_tokens"], cell.serve["sample_requests"])
        t1 = time.perf_counter()
        gaps = harness.compare(cell, served.params, picked, controls=controls)
        out = {"seed": seed, "rate": cell.serve["rate"], **gaps,
               "correct": {k: harness.judge(cell.serve, v, picked)[0] for k, v in gaps.items()},
               "sampled_requests": len(picked),
               "sampled_tokens": sum(len(f.tokens) for f in picked),
               "setup_s": run.setup_s, "compare_s": time.perf_counter() - t1,
               "compiles_window": served.compiles_window,
               "peak_bytes_in_use": served.memory_peak}
        for m in harness.metric_names(args.workload, False):
            v = harness.reader(m["name"])(run)
            out[m["name"]] = v
        print(json.dumps(out), flush=True)
        del served, run, picked
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
