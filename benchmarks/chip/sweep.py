#!/usr/bin/env python3
"""Find a cell's knee: serve its traffic at several fixed rates in one
process and print, per rate, what was offered against what was served.

    python3 benchmarks/chip/sweep.py --workload granite-20b.chat \\
        --rates 4,6,8,10 --seconds 20 --seed 5 [--describe-trace 1]

Each rate gets a fresh radix cache, the cell's own pre-window traffic and a
window of `--seconds`; the scheduler is drained between rates. The knee is
the highest rate whose backlog at the close stays near zero and whose
completions keep up with arrivals. With `--describe-trace 1` the first
window's first seconds are profiled, and the trace's planes, lines and first
events (``trace_describe.txt``) and its reduction (``trace_summary.json``)
are written under ``--out`` for a look by hand.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--describe-trace", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / ".bench_out"),
                    help="directory for the trace description")
    args = ap.parse_args(argv)

    import numpy as np

    import harness
    import traffic_gen
    from repro.core.runtime import Runtime
    from repro.serve.scheduler import Request

    cell = harness.load_cell(args.workload)
    devices = harness.check_device(cell.chips)
    harness.enable_cache()
    prog = cell.program
    params = cell.reference.make_weights(prog, args.seed)
    pre = cell.serve["pre_seconds"]
    with Runtime("jaxdev") as rt:
        sched = harness.build_scheduler(cell, params, rt)
        harness.warm(sched, Request, cell.traffic, prog["vocab_size"], args.seed)
        print(f"set-up {time.perf_counter() - T_START:.1f}s", flush=True)
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            sched.prefix.reset()
            items = traffic_gen.schedule(cell.traffic, rate=rate, pre=pre,
                                         seconds=args.seconds, seed=args.seed + k,
                                         vocab=prog["vocab_size"])
            d = harness.Driver(sched, Request, items)
            compiles: list = []
            harness._listen_compiles(compiles)
            t_open = harness.clock() + pre
            t_close = t_open + args.seconds
            d.run(t_open, t_open)
            n0 = len(compiles)
            tracer = harness._Tracer(bool(args.describe_trace) and k == 0, t_open,
                                     rt.tracer)
            d.run(t_open, t_close, hook=tracer.hook)
            tracer.stop()
            if tracer.state == "done":
                import trace_reduce

                out = Path(args.out)
                out.mkdir(parents=True, exist_ok=True)
                (out / "trace_describe.txt").write_text(trace_reduce.describe(tracer.dir))
                summ = tracer.summary()
                (out / "trace_summary.json").write_text(json.dumps(
                    {k2: v for k2, v in summ.items() if k2 != "op_s"} | {
                        "op_s_top": sorted(summ.get("op_s", {}).items(), key=lambda kv: -kv[1])[:60]},
                    indent=1))
            due = [r for r in d.requests.values() if t_open <= r.due < t_close]
            n_due = len(due)
            waits = [((r.first if r.first and r.first <= t_close else t_close) - r.due) for r in due]
            unadmitted = sum(1 for r in due if r.first is None or r.first > t_close)
            done = sum(1 for r in d.requests.values() if r.finished and t_open <= r.finished <= t_close)
            toks = sum(r.n_in for r in d.requests.values())
            tpots = [(r.last - r.first) / (r.n_window - 1) for r in due if r.n_window >= 2]
            occ = [len(s.slots) for s in d.steps if t_open <= s.t0 and s.t1 <= t_close]
            print(json.dumps({
                "rate": rate, "due": n_due, "finished_per_s": done / args.seconds,
                "output_tok_s": toks / args.seconds,
                "ttft_p50_ms": float(np.median(waits)) * 1e3 if waits else None,
                "ttft_p95_ms": float(np.percentile(waits, 95)) * 1e3 if waits else None,
                "tpot_p95_ms": float(np.percentile(tpots, 95)) * 1e3 if tpots else None,
                "waiting_at_close": unadmitted,
                "mean_active_slots": float(np.mean(occ)) if occ else 0.0,
                "compiles_in_window": len(compiles) - n0,
                "peak_bytes_in_use": harness.peak_bytes(devices),
            }), flush=True)
            d.drain()
    return 0


if __name__ == "__main__":
    sys.exit(main())
