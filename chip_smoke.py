#!/usr/bin/env python3
"""Smoke test of the paged serving path on a TPU.

    python chip_smoke.py              # one chip: kernel phase, then serve phase
    python chip_smoke.py --chips 4    # four chips: paged serve vs a 4-worker fleet

It serves gemma3-1b at full width, with random weights from PRNGKey(0),
through the entry point a user calls (`repro.launch.serve.main`), and checks
what comes out. The first line of output names the device; the last line is
``{"ok": true, "device": {...}}``, printed only when every phase passed. On a
device that is not a TPU it runs nothing and exits 1; a failed check raises
and exits 1. The times it prints are smoke timings of one run, compilation
included: they are not metrics.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.metadata
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.decode_attention import decode_attention  # noqa: E402
from repro.kernels.ops import on_tpu  # noqa: E402
from repro.kernels.paged_decode_attention import paged_decode_attention  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.common import dtype_of  # noqa: E402
from repro.serve.scheduler import Request  # noqa: E402

ARCH = "gemma3-1b"
MAX_BATCH = 8
PAGE = 16
NEW_TOKENS = 48
#: prompt lengths: below, inside and past gemma3's 512-token window, so the
#: window layers wrap while prefill compiles stay three
SHORT, MID, LONG = 96, 384, 640
#: the shared head: the window's 32 pages plus half a page, so that a cache
#: hit forks 32 pages by reference and copies the half-filled boundary page
HEAD = 512 + PAGE // 2
MAX_LEN = LONG + NEW_TOKENS


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def check(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


def device_info() -> dict:
    devices = jax.devices()
    stats = devices[0].memory_stats() or {}
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "jax": jax.__version__,
        "libtpu": _version("libtpu"),
        "bytes_limit": stats.get("bytes_limit"),
    }


def _version(package: str):
    try:
        return importlib.metadata.version(package)
    except importlib.metadata.PackageNotFoundError:
        return None


class CompileMeter:
    """Sums XLA backend compile time (a persistent-cache load included) as
    JAX reports it; fleet workers compile from several threads."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self._lock = threading.Lock()

    def __call__(self, event: str, duration: float, **_):
        if event == self.EVENT:
            with self._lock:
                self.seconds += duration
                self.programs += 1


@contextlib.contextmanager
def smoke_timing(label: str):
    t0 = time.perf_counter()
    yield
    print(f"smoke timing (not a metric): {label} took "
          f"{time.perf_counter() - t0:.1f}s wall, compilation included", flush=True)


# -- kernel phase --------------------------------------------------------------

def kernel_phase(cfg) -> None:
    """Run the Pallas decode kernels, compiled on a TPU (interpreted
    elsewhere), at the model's widths, and compare each with its
    `kernels/ref.py` oracle on the same inputs."""
    dtype = dtype_of(cfg.compute_dtype)
    # The kernels return `dtype`. In bfloat16 (8 significant bits, relative
    # step 2**-8) the kernel and the oracle round differently-ordered float32
    # sums to neighbouring values, and the chip's matrix unit rounds the
    # float32 softmax weights to bfloat16 before the value product: 2e-2
    # bounds both on outputs of magnitude ~1. In float32 only the summation
    # order differs: 2e-5. These are the tolerances of tests/test_kernels.py.
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    window, B = cfg.sliding_window, MAX_BATCH
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))

    def rand(shape):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    q = rand((B, H, hd))
    cases = []
    # dense caches of the serial engine: global layers hold MAX_LEN positions,
    # window layers a ring of `window`
    for S in (MAX_LEN, window):
        k, v = rand((B, S, KV, hd)), rand((B, S, KV, hd))
        pos = jax.random.randint(next(keys), (B,), 0, S)
        cases.append((
            f"decode_attention S={S}",
            lambda q, k, v, pos: decode_attention(q, k, v, pos),
            (q, k, v, pos),
            ref.decode_attention(q, k, v, pos),
        ))
    # the paged pool: every slot owns MAX_LEN positions' worth of scattered
    # pages (page 0 is the null page); slot 0 sits inside its first page and
    # slot 1 at the end of its last, the rest anywhere
    n_pages = -(-MAX_LEN // PAGE)
    P = B * n_pages + 1
    k_pool, v_pool = rand((P, PAGE, KV, hd)), rand((P, PAGE, KV, hd))
    table = (1 + jax.random.permutation(next(keys), B * n_pages)).reshape(B, n_pages)
    table = table.astype(jnp.int32)
    pos = jax.random.randint(next(keys), (B,), 0, n_pages * PAGE)
    pos = pos.at[0].set(5).at[1].set(n_pages * PAGE - 1)
    for w in (0, window):
        cases.append((
            f"paged_decode_attention window={w}",
            lambda q, kp, vp, t, p, w=w: paged_decode_attention(q, kp, vp, t, p, window=w),
            (q, k_pool, v_pool, table, pos),
            ref.paged_decode_attention(q, k_pool, v_pool, table, pos, window=w),
        ))

    for name, fn, args, want in cases:
        compiled = jax.jit(fn).lower(*args).compile()
        if on_tpu():
            check("tpu_custom_call" in compiled.as_text(),
                  f"{name}: no tpu_custom_call in the compiled program")
        got = np.asarray(compiled(*args), np.float32)
        want = np.asarray(want, np.float32)
        check(bool(np.isfinite(got).all()), f"{name}: non-finite output")
        err = float(np.max(np.abs(got - want)))
        check(np.allclose(got, want, rtol=tol, atol=tol),
              f"{name}: max |kernel - oracle| = {err} exceeds {tol}")
        print(f"kernel {name}: compiled={on_tpu()} max |kernel - oracle| = "
              f"{err:.3g} (tolerance {tol})", flush=True)


# -- serve phases --------------------------------------------------------------

def smoke_requests(vocab_size: int) -> list:
    """12 requests of NEW_TOKENS tokens each, with prompts of the three
    lengths. Six of them (rid ``s*``) are LONG prompts that open with one
    shared HEAD-token head. Four of those come after the first MAX_BATCH
    requests, so they are admitted once a request holding the head has
    finished and committed it to the prefix cache."""
    rng = np.random.default_rng(0)
    head = rng.integers(1, vocab_size, HEAD).tolist()
    kinds = "usuusuuussss"
    unique_lengths = iter([SHORT, MID, LONG] * 2)
    requests = []
    for i, kind in enumerate(kinds):
        if kind == "s":
            prompt = head + rng.integers(1, vocab_size, LONG - HEAD).tolist()
        else:
            prompt = rng.integers(1, vocab_size, next(unique_lengths)).tolist()
        requests.append(Request(rid=f"{kind}{i}", prompt=prompt, max_new_tokens=NEW_TOKENS))
    return requests


def _serve_argv(mode: str, reduced: bool, *extra: str) -> list:
    argv = ["--arch", ARCH, "--mode", mode, "--kv-mode", "paged",
            "--max-batch", str(MAX_BATCH), "--page-size", str(PAGE), *extra]
    return argv + (["--reduced"] if reduced else [])


def check_served(results: dict, requests, label: str) -> None:
    check(set(results) == {r.rid for r in requests}, f"{label}: requests missing")
    for r in requests:
        res = results[r.rid]
        check("error" not in res, f"{label}: {r.rid} failed: {res.get('error')}")
        check(res["finish_reason"] == "length" and len(res["tokens"]) == r.max_new_tokens,
              f"{label}: {r.rid} finished {res['finish_reason']!r} after "
              f"{len(res['tokens'])} of {r.max_new_tokens} tokens")


def _serve(label: str, argv: list, requests) -> serve.ServeReport:
    # jax_debug_nans raises on the first NaN in any array an execution unit
    # returns: the serial engine's logits, and every layer's KV pages, which
    # a NaN anywhere before the last layer reaches within one token
    with smoke_timing(f"serve run {label!r}"), jax.debug_nans(True):
        report = serve.main(argv, requests=requests)
    check_served(report.results, requests, label)
    return report


def _agreement(a: dict, b: dict, requests) -> tuple:
    """Tokens on which two runs agree, and the rids whose tokens differ."""
    agree = sum(
        int(x == y)
        for r in requests
        for x, y in zip(a[r.rid]["tokens"], b[r.rid]["tokens"])
    )
    return agree, [r.rid for r in requests if a[r.rid]["tokens"] != b[r.rid]["tokens"]]


def serve_phase(reduced: bool = False) -> dict:
    """gemma3-1b through `serve.main`: the paged path with the prefix cache
    off (every admission a miss: the whole prompt as the tail), then with
    it on (hits, shared pages, copy-on-write) on the same requests. Both
    prefill through the tail prefill over one paged layout. Reports how
    many tokens agree between them, and with the serial engine on two
    requests."""
    requests = smoke_requests(get_config(ARCH, reduced=reduced).vocab_size)
    total = NEW_TOKENS * len(requests)
    paged = _serve("paged", _serve_argv("continuous", reduced), requests)
    prefix = _serve("paged+prefix",
                    _serve_argv("continuous", reduced, "--prefix-cache"), requests)
    hits = prefix.stats["prefix"]["hits"]
    check(hits > 0, "paged+prefix: the prefix cache never hit")
    agree, differ = _agreement(paged.results, prefix.results, requests)
    print(f"serve: paged and paged+prefix agree on {agree}/{total} tokens; "
          f"requests that differ: {differ}; prefix cache hits {hits}", flush=True)
    # A hit prefills only its tail, against the prefix's K/V read back from
    # pool pages, where the cache-off run prefills the whole prompt; in
    # bfloat16 a near-tie between two logits can then flip a greedy token.
    # That is rare per request; a fault in shared pages or copy-on-write
    # diverges every request that takes it, the cache hits (a third). More
    # than a quarter diverging fails.
    check(len(differ) <= len(requests) // 4,
          f"paged and paged+prefix tokens differ for {differ}")

    # two LONG prompts (past the window: the serial engine's ring wraps)
    pair = [r for r in requests if len(r.prompt) == LONG][:2]
    serial = _serve("serial", _serve_argv("serial", reduced), pair)
    serial_agree, _ = _agreement(serial.results, paged.results, pair)
    # reported, not asserted: with random weights a near-tie can flip a
    # greedy token between the dense and paged kernels
    print(f"serve: {serial_agree}/{NEW_TOKENS * len(pair)} tokens of "
          f"{[r.rid for r in pair]} agree between the serial engine and the "
          "paged path", flush=True)
    return {"agree": agree, "differ": differ, "serial_agree": serial_agree,
            "prefix_hits": hits}


def fleet_phase(reduced: bool = False, workers: int = 4) -> dict:
    """The paged run on one chip, then the same requests through a fleet of
    `workers` replicas behind the router, one per device: the tokens must be
    identical, and every worker must sit on its own device and serve."""
    requests = smoke_requests(get_config(ARCH, reduced=reduced).vocab_size)
    one = _serve("paged", _serve_argv("continuous", reduced), requests)
    fleet = _serve(f"fleet x{workers}",
                   _serve_argv("fleet", reduced, "--workers", str(workers)), requests)
    differ = [r.rid for r in requests
              if one.results[r.rid]["tokens"] != fleet.results[r.rid]["tokens"]]
    check(not differ, f"fleet and one-chip tokens differ for {differ}")
    devices = fleet.stats["per_worker_device"]
    settled = fleet.stats["per_worker_settled"]
    for idx in sorted(devices):
        print(f"fleet worker {idx}: device {devices[idx]}, settled {settled[idx]}",
              flush=True)
    check(len(set(devices.values())) == workers,
          f"fleet workers share devices: {devices}")
    check(all(n > 0 for n in settled.values()), f"a fleet worker served nothing: {settled}")
    print(f"fleet: {workers} workers on {workers} devices agree with the "
          f"one-chip run on all {len(requests)} requests", flush=True)
    return {"devices": devices, "settled": settled}


# -- entry point ---------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Smoke test of the paged serving path on a TPU.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the paged serve on one chip and the same "
                    "requests through a fleet of four one-chip workers")
    args = ap.parse_args(argv)

    dev = device_info()
    print("device: " + json.dumps(dev), flush=True)
    if dev["platform"] != "tpu":
        print(f"chip_smoke: the device is {dev['platform']!r}, not a TPU; nothing ran",
              file=sys.stderr)
        return 1
    if dev["count"] < args.chips:
        print(f"chip_smoke: {args.chips} chips asked for, {dev['count']} present",
              file=sys.stderr)
        return 1

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    meter = CompileMeter()
    jax.monitoring.register_event_duration_secs_listener(meter)
    if args.chips == 4:
        with smoke_timing("fleet phase"):
            fleet_phase(workers=args.chips)
    else:
        with smoke_timing("kernel phase"):
            kernel_phase(get_config(ARCH))
        with smoke_timing("serve phase"):
            serve_phase()
    print(f"compile: {meter.programs} programs, {meter.seconds:.1f}s in the XLA "
          "backend (persistent-cache loads included)", flush=True)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    print(f"peak_bytes_in_use per device: {peaks}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"], "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
