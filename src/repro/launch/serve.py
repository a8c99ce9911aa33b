"""Serving driver: load (or init) weights and serve a synthetic workload
through the serial engine, the continuous-batching scheduler, or a
data-parallel worker fleet, on a registry-built Runtime (no concrete-backend
imports here; fleet mode assembles its localsim world inside serve/router).

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
        --mode continuous --max-batch 8 --requests 16 [--backend jaxdev] \
        [--kv-mode paged --page-size 16 --sync-interval 8 --pool-pages N] \
        [--prefix-cache --prefix-share 0.5]

    # data-parallel fleet: router + N worker instances (paper §3.1.1)
    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --reduced \
        --mode fleet --workers 2 --max-batch 4 --requests 16

``--kv-mode paged`` serves from a paged KV-cache pool (block-pool tensors
behind a scheduler-owned page table, every layer paged alike, admission
bounded by free pages) with the device-resident decode loop
(`--sync-interval` fused ticks per host sync); each admission prefills the
prompt's uncached tail against pages gathered from the pool, the whole
prompt when nothing is cached. ``--prefix-cache`` adds the radix cache that
finds cached prefixes. ``--kv-mode dense`` is the per-slot dense-cache
baseline. Both apply per worker in fleet mode.

`main` returns a `ServeReport`: the tokens and finish reason of every
request, as the chosen mode produced them, so programmatic callers (the
chip smoke test) check what this entry point served.

The channel-driven multi-instance front door (2 producers + 1 server over
the localsim fabric) is wired in examples/serve_demo.py.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, Optional, Sequence

import jax
import numpy as np

from repro.configs import get_config
from repro.core.runtime import Runtime
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import ContinuousBatchingScheduler, Request
from repro.serve.workload import shared_prefix_requests, synthetic_requests
from repro.train import checkpoint as ckpt


@dataclasses.dataclass
class ServeReport:
    """What one `main` call served."""

    #: rid -> {"tokens": [...], "finish_reason": str} (or {"error": str})
    results: Dict[str, dict]
    #: mode-specific counters. Continuous mode: the radix cache's "prefix"
    #: counters (None without it); fleet mode: the router's stats, with
    #: each worker's device under "per_worker_device"
    stats: dict = dataclasses.field(default_factory=dict)


def main(argv=None, *, requests: Optional[Sequence[Request]] = None) -> ServeReport:
    """Serve a workload as the command line says. `requests`, when given,
    replaces the synthetic workload the flags would build."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--backend", default="jaxdev", help="registry backend for the Runtime")
    ap.add_argument("--mode", choices=("serial", "continuous", "fleet"), default="continuous")
    ap.add_argument("--workers", type=int, default=2,
                    help="fleet mode: worker instances spawned by the router")
    ap.add_argument("--msg-size", type=int, default=None,
                    help="fleet mode: channel message size in bytes (default: "
                    "sized to fit the workload's longest possible request)")
    ap.add_argument("--kv-mode", choices=("dense", "paged"), default="dense",
                    help="continuous mode: dense per-slot caches, or the paged "
                    "KV pool + device-resident decode loop")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV pool page size in cache positions (paged mode)")
    ap.add_argument("--sync-interval", type=int, default=8,
                    help="device decode ticks per host sync (paged mode)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="physical KV pool pages (default: every slot can "
                    "hold a full-length sequence)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged mode: refcounted radix prefix cache — shared "
                    "prompt prefixes are forked by page reference, so the "
                    "tail prefill skips them (fleet mode adds "
                    "prefix-affinity routing)")
    ap.add_argument("--prefix-share", type=float, default=0.0,
                    help="fraction of requests opening with a shared system "
                    "prompt (the workload prefix caching exists for); 0 "
                    "keeps the fully-unique synthetic workload")
    ap.add_argument("--max-batch", type=int, default=8, help="scheduler slots (continuous mode)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced)
    model = build(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        restored, _ = ckpt.restore(args.ckpt_dir, {"params": params})
        params = jax.tree_util.tree_map(jax.numpy.asarray, restored["params"])
        print(f"restored weights from {args.ckpt_dir}")

    if requests is not None:
        requests = list(requests)
    elif args.prefix_share > 0:
        requests = shared_prefix_requests(
            cfg.vocab_size,
            args.requests,
            prefix_len=max(1, args.prompt_len // 2),
            prefix_share=args.prefix_share,
            tail_range=(1, max(2, args.prompt_len // 2 + 1)),
            steps_range=(max(1, args.steps // 2), args.steps + 1),
        )
    else:
        requests = synthetic_requests(
            cfg.vocab_size,
            args.requests,
            prompt_range=(max(1, args.prompt_len // 2), args.prompt_len + 1),
            steps_range=(max(1, args.steps // 2), args.steps + 1),
        )
    total_tokens = sum(r.max_new_tokens for r in requests)
    prefix = cfg.vision_tokens if cfg.family == "vlm" else 0
    max_len = prefix + max(len(r.prompt) + r.max_new_tokens for r in requests)

    t0 = time.time()
    if args.mode == "fleet":
        from repro.serve.router import run_fleet

        # default msg_size: room for the longest admissible request wire
        # (~6 bytes per prompt token + JSON framing), rounded up
        msg_size = args.msg_size or max(512, 128 + 8 * max_len)
        out = run_fleet(
            model, params, requests, n_workers=args.workers,
            max_batch=args.max_batch, max_len=max_len, msg_size=msg_size,
            kv_mode=args.kv_mode, page_size=args.page_size,
            pool_pages=args.pool_pages, sync_interval=args.sync_interval,
            prefix_cache=args.prefix_cache, worker_backend=args.backend,
        )
        for r in requests:
            res = out.results[r.rid]
            if "error" in res:
                print(f"{r.rid}: ERROR {res['error']}")
            else:
                print(f"{r.rid}: {res['tokens'][:8]}... ({res['finish_reason']})")
        stats = out.stats
        print(f"fleet: {stats['workers_spawned']} workers, per-worker settled "
              f"{stats['per_worker_settled']}, restarted {stats['restarted']}")
        if args.prefix_cache:
            for idx, pstats in sorted(stats.get("per_worker_prefix", {}).items()):
                if pstats:
                    print(f"  worker {idx} prefix cache: hit_rate="
                          f"{pstats['hit_rate']:.2f} cached_pages="
                          f"{pstats['cached_pages']}")
        dt = time.time() - t0
        print(f"served {len(requests)} requests / {total_tokens} tokens in {dt:.2f}s "
              f"({total_tokens / dt:.1f} tok/s, mode=fleet, workers={args.workers}, "
              f"backend={args.backend})")
        return ServeReport(results=out.results, stats=stats)
    # context-managed Runtime: the default processing unit is finalized on
    # exit, so repeated invocations never leak backend worker threads
    results: Dict[str, dict] = {}
    stats: dict = {}
    with Runtime(args.backend) as runtime:
        if args.mode == "serial":
            engine = ServeEngine(model, params, max_len=max_len, runtime=runtime)
            for r in requests:
                prompt = np.asarray([r.prompt], dtype=np.int32)
                result = engine.generate(prompt, steps=r.max_new_tokens)
                tokens = result.tokens[0].tolist()
                results[r.rid] = {"tokens": tokens, "finish_reason": "length"}
                print(f"{r.rid}: {tokens[:8]}...")
        else:
            sched = ContinuousBatchingScheduler(
                model, params, max_batch=args.max_batch, max_len=max_len, runtime=runtime,
                kv_mode=args.kv_mode, page_size=args.page_size,
                pool_pages=args.pool_pages, sync_interval=args.sync_interval,
                prefix_cache=args.prefix_cache,
            )
            finished = sched.serve(requests)
            for r in requests:
                fin = finished[r.rid]
                results[r.rid] = {"tokens": fin.tokens, "finish_reason": fin.finish_reason}
                print(f"{fin.rid}: {fin.tokens[:8]}... ({fin.finish_reason})")
            print(f"scheduler: {sched.ticks} decode ticks for {len(requests)} requests"
                  f" (kv_mode={args.kv_mode})")
            prog = sched.active_progress()
            stats = {"prefix": prog.prefix}
            if args.kv_mode == "paged":
                print(f"kv pool: {prog.pages_used} pages used / "
                      f"{prog.pages_free} free after drain")
                if prog.prefix is not None:
                    print(f"prefix cache: hit_rate={prog.prefix['hit_rate']:.2f} "
                          f"({prog.prefix['hits']}/{prog.prefix['lookups']} requests, "
                          f"{prog.prefix['hit_tokens']}/{prog.prefix['queried_tokens']}"
                          f" tokens), {prog.prefix['cached_pages']} cached pages, "
                          f"{prog.prefix['evictions']} evictions")
    dt = time.time() - t0
    print(f"served {len(requests)} requests / {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens / dt:.1f} tok/s, mode={args.mode}, backend={args.backend})")
    return ServeReport(results=results, stats=stats)


if __name__ == "__main__":
    main()
