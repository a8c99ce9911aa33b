"""Benchmark driver — one module per paper table/figure:

    PYTHONPATH=src python -m benchmarks.run               # all
    PYTHONPATH=src python -m benchmarks.run channels      # one
    PYTHONPATH=src python -m benchmarks.run --smoke       # CI: tiny configs,
                                                          # verifies the scripts
                                                          # still run end-to-end
    PYTHONPATH=src python -m benchmarks.run --repeats 5   # warmup + median-of-5

Paper artifact map:
    bench_channels     -> Fig. 8   (ping-pong goodput, 2 comm backends)
    bench_inference    -> Table 2  (heterogeneous inference consistency)
    bench_tasking_fib  -> Fig. 9   (fine-grained tasking overhead)
    bench_jacobi       -> Figs. 10/11 (coarse tasking + strong/weak scaling)
    bench_rooflines    -> EXPERIMENTS.md §Roofline source table
Writes benchmarks/results.csv (benchmarks/results_smoke.csv with --smoke).
The serving stack is measured on the chip by benchmarks/chip/.
"""
from __future__ import annotations

import argparse
import csv
import inspect
import time

from repro.launch.compile_cache import enable_compile_cache

from ._agg import median_rows
from . import (
    bench_channels,
    bench_inference,
    bench_jacobi,
    bench_rooflines,
    bench_tasking_fib,
)

ALL = {
    "channels": bench_channels.run,
    "inference": bench_inference.run,
    "tasking_fib": bench_tasking_fib.run,
    "jacobi": bench_jacobi.run,
    "rooflines": bench_rooflines.run,
}


def _median_merge(rows_per_repeat: list[list[dict]]) -> list[dict]:
    """Positional field-wise median across repeats (every repeat produces
    the same row sequence; non-numeric fields come from the first run)."""
    merged = []
    for rows in zip(*rows_per_repeat):
        row = median_rows(list(rows))
        row["repeats"] = len(rows_per_repeat)
        merged.append(row)
    return merged


def _run_bench(fn, *, smoke: bool, repeats: int) -> list[dict]:
    kwargs = {"smoke": True} if smoke else {}
    if repeats > 1 and "repeats" in inspect.signature(fn).parameters:
        # the bench aggregates internally (and runs its own warmup pass)
        return fn(**kwargs, repeats=repeats)
    if repeats > 1:
        fn(**kwargs)  # warmup iteration: compile caches, page caches — discarded
        return _median_merge([fn(**kwargs) for _ in range(repeats)])
    return fn(**kwargs)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("names", nargs="*", help=f"subset of {list(ALL)} (default: all)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--repeats", type=int, default=1,
                    help="measured repetitions per bench (plus one warmup "
                    "iteration); rows report the field-wise median")
    args = ap.parse_args()
    enable_compile_cache()
    names = args.names or list(ALL)
    all_rows: list[dict] = []
    for name in names:
        print(f"=== bench: {name}{' (smoke)' if args.smoke else ''} ===")
        t0 = time.monotonic()
        rows = _run_bench(ALL[name], smoke=args.smoke, repeats=args.repeats)
        print(f"=== {name}: {len(rows)} rows in {time.monotonic() - t0:.1f}s ===\n")
        all_rows.extend(rows)

    fields: list[str] = []
    for row in all_rows:
        for k in row:
            if k not in fields:
                fields.append(k)
    out = "benchmarks/results_smoke.csv" if args.smoke else "benchmarks/results.csv"
    with open(out, "w", newline="") as f:
        writer = csv.DictWriter(f, fieldnames=fields)
        writer.writeheader()
        writer.writerows(all_rows)
    print(f"wrote {out} ({len(all_rows)} rows)")


if __name__ == "__main__":
    main()
