"""Serving throughput/latency: serial engine vs continuous batching vs
paged continuous batching vs the data-parallel fleet.

Same workload (requests of varied prompt/decode lengths, all submitted at
t=0) through the serve paths:

* serial   — `ServeEngine`, one request end-to-end at a time;
* continuous — `ContinuousBatchingScheduler` (dense KV), admit-on-free-slot,
  one vmapped decode tick across all active slots, host sync every tick;
* continuous_paged — paged KV pool + device-resident decode loop: KV lives
  in a shared block pool behind a page table, and `sync_interval` fused
  decode+sample ticks run as one execution unit with tokens/positions/done
  flags staying on device between host sync points;
* fleet — router + FLEET_WORKERS worker instances over the localsim
  InstanceManager, the total slot budget split across workers. Fleet wall
  time INCLUDES instance spawn and per-worker compilation (each pass builds
  a fresh fleet — that end-to-end cost is the fleet story); on one CPU
  device the workers time-share the hardware, so this row measures the
  orchestration overhead ceiling, not a speedup.

With ``prefix_cache=True`` two more rows run on a *shared-system-prompt*
workload (PREFIX_SHARE of the requests open with the same PREFIX_LEN-token
prompt): ``paged_prefix_off`` (plain paged) vs ``paged_prefix_on`` (the
refcounted radix cache). Each measured pass resets the cache, warms it with
one request per distinct system prompt (the deploy-time state of a real
server), then serves the burst; rows report total admission/prefill time
and TTFT split by shared ("-s", cache-hit) vs unique ("-u") requests, plus
the cache's token-level hit rate. Outputs are asserted token-identical
between the two rows before any timing is trusted.

Reports aggregate decode tokens/s, per-request latency (submission at t=0 to
reply, i.e. queueing included — the number a client sees), and
**time-to-first-token** (submission to the first output token existing).
Paged output is asserted token-identical to the dense scheduler before any
timing is trusted.

Serve numbers swing badly under machine load, so measurement is
median-of-N: a warmup pass compiles everything, then `repeats` measured
passes per mode are aggregated field-wise by median (benchmarks/run.py
--repeats N, default 1). Writes benchmarks/BENCH_serve.json and contributes
rows to benchmarks/results.csv via benchmarks/run.py.
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.runtime import Runtime
from repro.models import build
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import ContinuousBatchingScheduler
from repro.serve.workload import shared_prefix_requests, synthetic_requests

from ._agg import median_rows

ARCH = "gemma3-1b"
N_REQUESTS = 12
MAX_BATCH = 8
PROMPT_RANGE = (4, 12)
STEPS_RANGE = (8, 24)
PAGE_SIZE = 16
SYNC_INTERVAL = 8  # empirically best on this workload's 8-24 step range
FLEET_WORKERS = 2
PREFIX_LEN = 512    # shared system prompt length (32 full pages) — long
                    # enough that prefill compute dominates dispatch overhead
                    # on the reduced config, as real system prompts do
PREFIX_SHARE = 0.75  # fraction of requests opening with it (spec floor: 0.5)
PREFIX_TAIL = (2, 8)  # unique tail tokens after the shared prompt


def _stats(values, prefix):
    arr = np.asarray(sorted(values))
    return {
        f"{prefix}_mean_s": round(float(arr.mean()), 4),
        f"{prefix}_p50_s": round(float(np.percentile(arr, 50)), 4),
        f"{prefix}_p95_s": round(float(np.percentile(arr, 95)), 4),
    }


def _run_serial(engine, requests):
    t0 = time.monotonic()
    latencies, ttfts = [], []
    tokens = {}
    for r in requests:
        result = engine.generate(
            np.asarray([r.prompt], dtype=np.int32),
            steps=r.max_new_tokens,
            on_first_token=lambda: ttfts.append(time.monotonic() - t0),
        )
        tokens[r.rid] = result.tokens[0].tolist()
        latencies.append(time.monotonic() - t0)  # queued since t0
    return time.monotonic() - t0, latencies, ttfts, tokens


def _run_continuous(sched, requests):
    from collections import deque

    backlog = deque(requests)
    t0 = time.monotonic()
    latencies, ttfts = [], []
    tokens = {}
    n_done = 0
    while n_done < len(requests):
        while backlog and sched.try_admit(backlog[0]):
            backlog.popleft()
            # admission runs the prefill: the request's first token exists now
            ttfts.append(time.monotonic() - t0)
        for fin in sched.step():
            latencies.append(time.monotonic() - t0)
            tokens[fin.rid] = fin.tokens
            n_done += 1
    return time.monotonic() - t0, latencies, ttfts, tokens


def _run_prefix_pass(sched, requests, warm_requests):
    """One measured pass of the shared-prompt workload: reset + rewarm the
    cache when the scheduler has one (deploy-time state: system prompts
    resident, per-burst traffic fresh), then serve, timing each admission
    (the prefill cost a prefix hit avoids) and per-rid TTFT. Returns the
    post-warm counter snapshot last, so the caller's per-pass hit rate
    covers the measured burst only (the warm request is a guaranteed full
    miss and would deflate it)."""
    from collections import deque

    s0 = None
    if sched.prefix is not None:
        sched.prefix.reset()
        for w in warm_requests:
            sched.serve([w])
        s0 = dict(sched.prefix.stats())
    backlog = deque(requests)
    t0 = time.monotonic()
    latencies, prefill_s, ttft_by_rid, tokens = [], [], {}, {}
    n_done = 0
    while n_done < len(requests):
        while backlog:
            rid = backlog[0].rid
            t_adm = time.monotonic()
            if not sched.try_admit(backlog[0]):
                break
            now = time.monotonic()
            prefill_s.append(now - t_adm)
            ttft_by_rid[rid] = now - t0
            backlog.popleft()
        for fin in sched.step():
            latencies.append(time.monotonic() - t0)
            tokens[fin.rid] = fin.tokens
            n_done += 1
    wall = time.monotonic() - t0
    return wall, latencies, prefill_s, ttft_by_rid, tokens, s0


class _TimingSink:
    """Client-facing fleet stream that timestamps every merged chunk."""

    def __init__(self):
        self.chunks = []
        self.stamps = []

    def push(self, chunk):
        self.stamps.append(time.monotonic())
        self.chunks.append(chunk)


def _run_fleet(spec, requests):
    from repro.serve.router import reassemble, run_fleet

    model, params, max_len = spec
    sink = _TimingSink()
    t0 = time.monotonic()
    run_fleet(
        model, params, requests, sink=sink, n_workers=FLEET_WORKERS,
        max_batch=max(1, MAX_BATCH // FLEET_WORKERS), max_len=max_len,
        stream_interval=4, launch_timeout=900,
    )
    wall = time.monotonic() - t0
    first_seen, last_seen = {}, {}
    for stamp, chunk in zip(sink.stamps, sink.chunks):
        rid = chunk.get("id")
        first_seen.setdefault(rid, stamp)
        last_seen[rid] = stamp
    ttfts = [t - t0 for t in first_seen.values()]
    latencies = [t - t0 for t in last_seen.values()]
    tokens = {
        rid: res["tokens"] for rid, res in reassemble(sink.chunks).items()
        if "error" not in res
    }
    return wall, latencies, ttfts, tokens


def _prefix_rows(model, params, cfg, runtime, *, smoke: bool, repeats: int):
    """The shared-prompt comparison: paged with vs without the radix cache.
    Returns (rows, summary_fields)."""
    from repro.serve.scheduler import Request

    p_len = 16 if smoke else PREFIX_LEN
    tail = (1, 4) if smoke else PREFIX_TAIL
    steps = (4, 8) if smoke else STEPS_RANGE
    # every request admits in the opening burst (n == slots), so TTFT is
    # admission-dominated and the hit/miss split is not washed out by
    # queueing time that both modes pay identically
    n_p = 4 if smoke else MAX_BATCH
    p_max_len = p_len + tail[1] + steps[1] + 1
    reqs = shared_prefix_requests(
        cfg.vocab_size, n_p, prefix_len=p_len, prefix_share=PREFIX_SHARE,
        tail_range=tail, steps_range=steps, seed=1,
    )
    total_tokens = sum(r.max_new_tokens for r in reqs)
    sys_prompt = next(r.prompt[:p_len] for r in reqs if "-s" in r.rid)
    # deploy-time warm state: one 2-token request pins the system prompt's
    # full pages into the cache before each measured pass
    warm = [Request(rid="warm-0", prompt=list(sys_prompt) + [1], max_new_tokens=2)]
    n_ps = -(-p_max_len // PAGE_SIZE)

    off = ContinuousBatchingScheduler(
        model, params, max_batch=MAX_BATCH, max_len=p_max_len,
        runtime=runtime, kv_mode="paged", page_size=PAGE_SIZE,
        sync_interval=SYNC_INTERVAL,
    )
    on = ContinuousBatchingScheduler(
        model, params, max_batch=MAX_BATCH, max_len=p_max_len,
        runtime=runtime, kv_mode="paged", page_size=PAGE_SIZE,
        sync_interval=SYNC_INTERVAL, prefix_cache=True,
        # headroom over the per-slot worst case so resident cache pages
        # do not force eviction churn mid-burst
        pool_pages=MAX_BATCH * n_ps + 1 + 2 * n_ps,
    )
    modes = [("paged_prefix_off", off), ("paged_prefix_on", on)]

    # warmup pass: compile every tail/prompt length, assert token identity
    warm_tokens = {}
    for mode, sched in modes:
        warm_tokens[mode] = _run_prefix_pass(sched, reqs, warm)[4]  # tokens
    mismatched = [
        rid for rid in warm_tokens["paged_prefix_off"]
        if warm_tokens["paged_prefix_on"].get(rid) != warm_tokens["paged_prefix_off"][rid]
    ]
    assert not mismatched, f"prefix-cache output diverged for {mismatched}"
    print(f"[serve] paged_prefix_on output token-identical across {n_p} requests")

    per_repeat = {mode: [] for mode, _ in modes}
    for _ in range(max(1, repeats)):
        for mode, sched in modes:
            wall, latencies, prefill_s, ttft_by_rid, _tokens, s0 = _run_prefix_pass(
                sched, reqs, warm
            )
            hit_rate = None  # cache-off rows: null, not a fake zero
            if sched.prefix is not None:
                # per-pass token-level rate over the measured burst only
                # (s0 was snapshotted after the warm request's full miss)
                s1 = sched.prefix.stats()
                queried = s1["queried_tokens"] - s0["queried_tokens"]
                hit = s1["hit_tokens"] - s0["hit_tokens"]
                hit_rate = round(hit / queried, 4) if queried else 0.0
            ttft_hit = [t for rid, t in ttft_by_rid.items() if "-s" in rid]
            ttft_miss = [t for rid, t in ttft_by_rid.items() if "-u" in rid]
            per_repeat[mode].append({
                "bench": "serve",
                "mode": mode,
                "arch": ARCH,
                "n_requests": n_p,
                "max_batch": MAX_BATCH,
                "sync_interval": SYNC_INTERVAL,
                "workers": 1,
                "repeats": max(1, repeats),
                "prefix_len": p_len,
                "prefix_share": PREFIX_SHARE,
                "total_decode_tokens": total_tokens,
                "wall_s": round(wall, 4),
                "tokens_per_s": round(total_tokens / wall, 2),
                "prefill_total_s": round(sum(prefill_s), 4),
                **_stats(latencies, "latency"),
                **_stats(list(ttft_by_rid.values()), "ttft"),
                "ttft_hit_mean_s": round(float(np.mean(ttft_hit)), 4),
                "ttft_miss_mean_s": round(float(np.mean(ttft_miss)), 4),
                "prefix_hit_rate": hit_rate,
            })
    rows = []
    for mode, _ in modes:
        row = median_rows(per_repeat[mode])
        rows.append(row)
        print(f"[serve] {mode:<16} prefill={row['prefill_total_s']:.3f}s  "
              f"ttft_hit={row['ttft_hit_mean_s']:.3f}s  "
              f"ttft_miss={row['ttft_miss_mean_s']:.3f}s  "
              f"hit_rate={row['prefix_hit_rate']}")
    by = {row["mode"]: row for row in rows}
    summary = {
        "prefix_share": PREFIX_SHARE,
        "prefix_hit_rate": by["paged_prefix_on"]["prefix_hit_rate"],
        "speedup_prefix_prefill": round(
            by["paged_prefix_off"]["prefill_total_s"]
            / max(by["paged_prefix_on"]["prefill_total_s"], 1e-9), 3,
        ),
        "speedup_prefix_ttft_hit": round(
            by["paged_prefix_off"]["ttft_hit_mean_s"]
            / max(by["paged_prefix_on"]["ttft_hit_mean_s"], 1e-9), 3,
        ),
    }
    print(f"[serve] prefix-cache prefill speedup: "
          f"{summary['speedup_prefix_prefill']:.2f}x, cache-hit TTFT speedup: "
          f"{summary['speedup_prefix_ttft_hit']:.2f}x "
          f"(share={PREFIX_SHARE}, hit_rate={summary['prefix_hit_rate']})")
    return rows, summary


def run(csv_writer=None, *, smoke: bool = False, repeats: int = 1,
        kv_mode: str = "both", prefix_cache: bool = False) -> list[dict]:
    if kv_mode not in ("dense", "paged", "both"):
        raise ValueError(f"kv_mode must be dense|paged|both, got {kv_mode!r}")
    n_requests = 4 if smoke else N_REQUESTS
    steps_range = (4, 8) if smoke else STEPS_RANGE
    cfg = get_config(ARCH, reduced=True)
    model = build(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    max_len = PROMPT_RANGE[1] + steps_range[1] + 1
    requests = synthetic_requests(
        cfg.vocab_size, n_requests, prompt_range=PROMPT_RANGE, steps_range=steps_range
    )
    total_tokens = sum(r.max_new_tokens for r in requests)

    with Runtime("jaxdev") as runtime:
        engine = ServeEngine(model, params, max_len=max_len, runtime=runtime)
        targets = [("serial", _run_serial, engine)]
        dense_sched = paged_sched = None
        if kv_mode in ("dense", "both"):
            dense_sched = ContinuousBatchingScheduler(
                model, params, max_batch=MAX_BATCH, max_len=max_len, runtime=runtime
            )
            targets.append(("continuous", _run_continuous, dense_sched))
        if kv_mode in ("paged", "both"):
            paged_sched = ContinuousBatchingScheduler(
                model, params, max_batch=MAX_BATCH, max_len=max_len, runtime=runtime,
                kv_mode="paged", page_size=PAGE_SIZE, sync_interval=SYNC_INTERVAL,
            )
            targets.append(("continuous_paged", _run_continuous, paged_sched))
        targets.append(("fleet", _run_fleet, (model, params, max_len)))

        # warmup: compile prefill (per distinct prompt length) and decode
        # units — and check paged + fleet output is token-identical to
        # dense/serial before any timing is trusted
        warm_tokens = {}
        for mode, runner, target in targets:
            warm_tokens[mode] = runner(target, requests)[3]
        reference = warm_tokens.get("continuous", warm_tokens["serial"])
        for checked in ("continuous_paged", "fleet"):
            if checked in warm_tokens:
                mismatched = [
                    rid for rid in reference
                    if warm_tokens[checked].get(rid) != reference[rid]
                ]
                assert not mismatched, f"{checked} output diverged for {mismatched}"
                print(f"[serve] {checked} output token-identical across "
                      f"{len(reference)} requests")

        # measured repeats are interleaved round-robin across modes so a
        # drift in background machine load biases every mode equally
        per_repeat: dict[str, list[dict]] = {mode: [] for mode, _, _ in targets}
        for _ in range(max(1, repeats)):
            for mode, runner, target in targets:
                wall, latencies, ttfts, _tokens = runner(target, requests)
                per_repeat[mode].append({
                    "bench": "serve",
                    "mode": mode,
                    "arch": ARCH,
                    "n_requests": n_requests,
                    "max_batch": 1 if mode == "serial" else MAX_BATCH,
                    "sync_interval": SYNC_INTERVAL if mode == "continuous_paged" else 1,
                    "workers": FLEET_WORKERS if mode == "fleet" else 1,
                    "repeats": max(1, repeats),
                    "total_decode_tokens": total_tokens,
                    "wall_s": round(wall, 4),
                    "tokens_per_s": round(total_tokens / wall, 2),
                    **_stats(latencies, "latency"),
                    **_stats(ttfts, "ttft"),
                })
        rows = []
        for mode, _, _ in targets:
            row = median_rows(per_repeat[mode])
            rows.append(row)
            print(f"[serve] {mode:<16} {row['tokens_per_s']:>8.1f} tok/s  "
                  f"wall={row['wall_s']:.2f}s  p50={row['latency_p50_s']:.2f}s  "
                  f"p95={row['latency_p95_s']:.2f}s  ttft_mean={row['ttft_mean_s']:.3f}s")

        prefix_summary = {}
        if prefix_cache:
            prows, prefix_summary = _prefix_rows(
                model, params, cfg, runtime, smoke=smoke, repeats=repeats
            )
            rows.extend(prows)

    by_mode = {row["mode"]: row for row in rows}
    dev = jax.devices()[0]
    # every timing here is from the device named in the file; from a CPU run
    # it is a CPU timing of the reduced preset, never a chip number
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "rows": rows,
        "repeats": max(1, repeats),
    }
    if "continuous" in by_mode:
        out["speedup_continuous_vs_serial"] = round(
            by_mode["continuous"]["tokens_per_s"] / by_mode["serial"]["tokens_per_s"], 3
        )
        out["ttft_serial_over_continuous"] = round(
            by_mode["serial"]["ttft_mean_s"]
            / max(by_mode["continuous"]["ttft_mean_s"], 1e-9), 3,
        )
    if "fleet" in by_mode:
        # informational: spawn + per-worker compile included; see docstring
        out["speedup_fleet_vs_serial"] = round(
            by_mode["fleet"]["tokens_per_s"] / by_mode["serial"]["tokens_per_s"], 3
        )
    out.update(prefix_summary)
    if "continuous_paged" in by_mode:
        out["speedup_paged_vs_serial"] = round(
            by_mode["continuous_paged"]["tokens_per_s"] / by_mode["serial"]["tokens_per_s"], 3
        )
        if "continuous" in by_mode:
            out["speedup_paged_vs_continuous"] = round(
                by_mode["continuous_paged"]["tokens_per_s"]
                / by_mode["continuous"]["tokens_per_s"], 3,
            )
            print(f"[serve] paged/continuous aggregate speedup: "
                  f"{out['speedup_paged_vs_continuous']:.2f}x")
    if smoke:
        # smoke runs verify the script, they are not reference numbers:
        # never overwrite the tracked BENCH_serve.json with them
        print("[serve] smoke mode: skipping BENCH_serve.json write")
        return rows
    path = os.path.join(os.path.dirname(__file__), "BENCH_serve.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"[serve] wrote {path}")
    return rows


if __name__ == "__main__":
    run()
