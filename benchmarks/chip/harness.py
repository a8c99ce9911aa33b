"""One cell of the chip benchmark: set-up, the open-loop window, the check.

The system under test is the repo's paged, prefix-cached continuous-batching
scheduler (`ContinuousBatchingScheduler(kv_mode="paged", prefix_cache=True)`
with the program's default page size and sync interval) inside a
``Runtime("jaxdev")``. Everything else here is the benchmark's own: the
weights (made from the seed), the traffic, the open-loop driver, the timing,
the reference the output is compared with, and the metric readers.

A cell is found by name: ``BENCHMARK.json`` names its configuration and
traffic, ``configs/<config>.json`` and ``traffic/<traffic>.json`` hold them,
and ``cells/<cell>.json`` holds the serving parameters (rate, batch, the
traffic served before the window, the correctness limit). Each metric is read by ``metrics/<metric>.py``.

A configuration names its plain reference with ``"reference": "<module>"``
(default ``refmodel``): the file ``<module>.py`` beside this one, loaded by
path (`load_reference`). The harness, the tools and the work counts reach the
reference only through `Cell.reference`. A reference module provides:

* ``make_weights(program, seed)``: weights from the seed, made on the
  device, in the tree layout the program under test takes;
* ``served_gaps(program, params, prompt, served, *, length, pad_rows,
  controls)``: ``{"program": widest gap of the served tokens, <control>:
  widest gap of the tokens each control puts first}`` (`compare`);
* ``QUANTIZERS``: a dict whose keys are the controls it can compute;
* optionally the counts `work` reads, each taking the program dict:
  ``matmul_params(p)``, ``attn_flops(p, ctx)``, ``attn_bytes(p, ctx)`` and
  ``decode_flops(p, ctx)``. Without them the readers built on them read
  nothing.

A metric reader ``metrics/<metric>.py`` defines ``read(run)``, returning a
number, or None when it finds nothing to read. Besides the benchmark's own
records on the `Run`, a reader of a traced run may read the program's spans,
``run.spans``: the `repro.core.tracing.Span` records (``name``, ``id``,
``parent_id``, ``t0_ns``, ``t1_ns`` on the clock of `time.perf_counter_ns`,
and ``attrs``, the program's counters) of the spans that opened and closed
inside the profiled part of the window. The program's tracer is on only
there, so ``run.spans`` is empty on untraced runs, and it is empty too where
the tracer's ring dropped any span (``run.spans_dropped``): a reader never
sees part of a window. A span or attribute a reader does not find means
nothing to read. So a configuration whose program records spans of its own
brings a reader of them as one new file and one new ``per_layer`` entry,
and edits no file that is here.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

import numpy as np

import traffic_gen

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: the persistent compilation cache: inside the checkout, at a fixed path
CACHE_DIR = ROOT / ".jax_cache"
#: scratch for traces, inside the checkout
OUT_DIR = ROOT / ".bench_out"
#: seconds of the window the traced run records
TRACE_SECONDS = 8.0
clock = time.perf_counter


def bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict  # configs/<config>.json
    traffic: dict  # traffic/<traffic>.json
    serve: dict  # cells/<cell>.json

    @property
    def program(self) -> dict:
        return self.config["program"]

    @functools.cached_property
    def reference(self) -> ModuleType:
        """The configuration's reference module (module docstring)."""
        return load_reference(self.config.get("reference", "refmodel"))


def load_cell(name: str, spec: Optional[dict] = None) -> Cell:
    spec = spec or bench()
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"unknown workload {name!r}")
    cell = Cell(
        name=name,
        chips=entry["chips"],
        config=json.loads((HERE / "configs" / f"{entry['config']}.json").read_text()),
        traffic=traffic_gen.load(entry["traffic"]),
        serve=json.loads((HERE / "cells" / f"{name}.json").read_text()),
    )
    cell.reference  # a reference named without its file stops here
    return cell


def load_reference(name: str) -> ModuleType:
    """The reference module ``<name>.py`` beside this file, loaded once per
    process and file (its jitted functions then compile once)."""
    path = HERE / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"reference {name!r}: no file {path}")
    key = f"reference_{name}"
    mod = sys.modules.get(key)
    if mod is None or Path(mod.__file__) != path:
        mod_spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(mod_spec)
        sys.modules[key] = mod  # before it runs, as an import would
        mod_spec.loader.exec_module(mod)
    return mod


def metric_names(cell: str, trace: bool, spec: Optional[dict] = None) -> List[dict]:
    """The metrics a run of `cell` reports: its end-to-end metrics, or with
    a trace its per-layer metrics (those listing it, or listing none and
    moving an end-to-end metric the cell reports)."""
    spec = spec or bench()
    e2e = [m for m in spec["end_to_end"] if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", [cell]) and m["moves"] in moved]


def reader(metric: str) -> Callable:
    path = HERE / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(f"metric_{metric}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# -- records a run keeps -----------------------------------------------------


@dataclasses.dataclass
class Req:
    due: float  # absolute clock
    prompt_len: int
    max_new: int
    admit_start: Optional[float] = None
    first: Optional[float] = None  # first token on the host
    last: Optional[float] = None
    n_window: int = 0  # tokens on the host by the window's close
    n_in: int = 0  # of those, tokens that came while the window was open
    finished: Optional[float] = None


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    ticks: int  # decode ticks the interval ran
    #: (context attended at the slot's first tick, tokens it decoded)
    slots: List[tuple]


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    cell: Cell
    peaks: dict
    setup_s: float
    t_open: float
    t_close: float
    requests: Dict[str, Req]
    steps: List[Step]
    admits: List[tuple]  # (t0, t1) of every try_admit that seated a request
    prefix_open: dict
    prefix_close: dict
    trace: Optional[dict] = None  # program_spans.summarize output
    trace_span: Optional[tuple] = None  # host-clock (t0, t1) traced
    #: the program's spans inside `trace_span`, none if any was dropped
    spans: list = dataclasses.field(default_factory=list)
    spans_dropped: int = 0  # spans the program's tracer ring dropped

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def in_window(self) -> List[Req]:
        return [r for r in self.requests.values() if self.t_open <= r.due < self.t_close]

    def traced_steps(self) -> List[Step]:
        if self.trace_span is None:
            return []
        a, b = self.trace_span
        return [s for s in self.steps if s.t0 >= a and s.t1 <= b]


# -- the driver --------------------------------------------------------------


class Driver:
    """Open loop over a precomputed schedule: each turn admits what is due
    while slots and pages allow, then runs one scheduler step. Every request
    is timed from its due time."""

    def __init__(self, sched, Request, items=()):
        self.sched = sched
        self.Request = Request
        self.queue = collections.deque(sorted(items, key=lambda it: it.due))
        self.requests: Dict[str, Req] = {}
        self.steps: List[Step] = []
        self.admits: List[tuple] = []
        self.finished: Dict[str, tuple] = {}  # rid -> (time, FinishedRequest)
        self._count: Dict[str, int] = {}
        self.t_open, self.t_close = -math.inf, math.inf

    def _deliver(self, rid: str, n_now: int, t: float) -> None:
        r = self.requests[rid]
        new = n_now - self._count.get(rid, 0)
        if new <= 0:
            return
        self._count[rid] = n_now
        if r.first is None:
            r.first = t
        if t <= self.t_close:
            r.last = t
            r.n_window += new
            if t >= self.t_open:
                r.n_in += new

    def run(self, t_open: float, t_close: float, *, hook=None,
            admit_more: bool = True) -> None:
        """Serve the schedule (due times relative to `t_open`) until
        `t_close`; with `admit_more` False, only finish what is running."""
        from jax.profiler import TraceAnnotation

        self.t_open, self.t_close = t_open, t_close
        queue = self.queue
        sched = self.sched
        for it in queue:  # every request is timed from its due time
            if it.rid not in self.requests:
                self.requests[it.rid] = Req(t_open + it.due, len(it.prompt), it.max_new)
        while True:
            now = clock()
            if now >= t_close:
                return
            if hook is not None:
                hook(now)
            while admit_more and queue and t_open + queue[0].due <= now:
                it = queue[0]
                rid = it.rid
                t0 = clock()
                with TraceAnnotation("try_admit"):
                    ok = sched.try_admit(self.Request(rid, it.prompt, it.max_new))
                t1 = clock()
                if not ok:
                    break
                queue.popleft()
                self.requests[rid].admit_start = t0
                self.admits.append((t0, t1))
                self._deliver(rid, 1, t1)
                now = t1
            if sched.active_count == 0:
                if not admit_more:
                    self._record_step(sched.step())  # finished at admission
                    return
                nxt = t_open + queue[0].due if queue else t_close
                wait = min(nxt, t_close) - clock()
                if wait > 0:
                    time.sleep(min(wait, 0.002))
                # then step(): it drains requests finished at admission
            before = sched.active_progress().requests
            t0 = clock()
            with TraceAnnotation("step"):
                fins = sched.step()
            t1 = clock()
            self._record_step(fins, before, t0, t1)

    def _record_step(self, fins, before=None, t0=0.0, t1=None) -> None:
        """Account one step: tokens each slot decoded, at what context, and
        when they reached the host."""
        t1 = clock() if t1 is None else t1
        after = self.sched.active_progress().requests
        done = {f.rid: f for f in fins}
        slots = []
        for rid, toks in (before or {}).items():
            n_now = len(done[rid].tokens) if rid in done else len(after.get(rid, toks))
            if n_now > len(toks):
                slots.append((self.requests[rid].prompt_len + len(toks), n_now - len(toks)))
        if before:
            self.steps.append(Step(t0, t1, self.sched.decoder.sync_interval, slots))
        for rid, toks in after.items():
            self._deliver(rid, len(toks), t1)
        for fin in fins:
            self._deliver(fin.rid, len(fin.tokens), t1)
            self.requests[fin.rid].finished = t1
            self.finished[fin.rid] = (t1, fin)

    def drain(self) -> None:
        self.run(0.0, math.inf, admit_more=False)


# -- set-up ------------------------------------------------------------------


def check_device(chips: int):
    """The platform, or SystemExit when no TPU or too few chips are found."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"no TPU found (JAX platform {devices[0].platform!r}): this benchmark "
            "measures only on a TPU"
        )
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX finds {len(devices)}")
    return devices[:chips]


def enable_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def build_scheduler(cell: Cell, params, runtime):
    from repro.configs import ArchConfig
    from repro.models import build
    from repro.serve.scheduler import ContinuousBatchingScheduler

    cfg = ArchConfig(name=cell.config["name"], **cell.program)
    s = cell.serve
    return ContinuousBatchingScheduler(
        build(cfg), params, max_batch=s["max_batch"], max_len=s["max_len"],
        runtime=runtime, kv_mode="paged", prefix_cache=True,
    )


def warm(sched, Request, traffic: dict, vocab: int, seed: int) -> None:
    """Run every tail length through `try_admit` and the decode interval,
    then empty the radix cache."""
    for it in traffic_gen.warm_items(traffic, vocab, seed):
        if not sched.try_admit(Request(it.rid, it.prompt, it.max_new)):
            raise RuntimeError(f"an idle scheduler refused warm-up request {it.rid}")
        while sched.active_count:
            sched.step()
        sched.step()  # drain requests finished at admission
    sched.prefix.reset()


def device_info(devices) -> dict:
    import jax

    stats = devices[0].memory_stats() or {}
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "jax": jax.__version__,
            "bytes_limit": stats.get("bytes_limit")}
    import importlib.metadata as md

    try:
        info["libtpu"] = md.version("libtpu")
    except md.PackageNotFoundError:
        info["libtpu"] = None
    return info


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def load_peaks(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# -- correctness -----------------------------------------------------------------


def sample(finished: Dict[str, tuple], t_open: float, t_close: float, seed: int,
           min_tokens: int, min_requests: int = 1) -> list:
    """Requests finished in the window, drawn from the seed: the one with
    the most served tokens first, then others until `min_tokens` tokens and
    `min_requests` requests are in."""
    done = [fin for (t, fin) in finished.values() if t_open <= t <= t_close]
    if not done:
        return []
    done.sort(key=lambda f: (-len(f.tokens), f.rid))
    picked = [done[0]]
    rest = done[1:]
    rng = np.random.default_rng([seed, 2])
    order = rng.permutation(len(rest))
    total = len(done[0].tokens)
    for i in order:
        if total >= min_tokens and len(picked) >= min_requests:
            break
        picked.append(rest[i])
        total += len(rest[i].tokens)
    return picked


def compare(cell: Cell, params, picked, controls=()) -> dict:
    """Widest reference gap over the picked requests' served tokens, and
    for each control the widest gap of the tokens it puts first."""
    s = cell.serve
    out: Dict[str, float] = {}
    for fin in picked:
        g = cell.reference.served_gaps(
            cell.program, params, fin.prompt, fin.tokens,
            length=s["max_len"], pad_rows=cell.traffic["output"]["max"],
            controls=controls,
        )
        for k, v in g.items():
            out[k] = max(out.get(k, 0.0), v)
    return out


def judge(serve: dict, gap: Optional[float], picked) -> tuple:
    """`correct` and the numbers it rests on, each beside its limit: the
    widest logit gap at most `gap_limit`, over a sample of at least
    `sample_tokens` tokens and `sample_requests` requests."""
    n_tokens = sum(len(f.tokens) for f in picked)
    checks = {
        "logit_gap": {"value": gap, "limit": serve["gap_limit"]},
        "sampled_tokens": {"value": n_tokens, "limit": serve["sample_tokens"]},
        "sampled_requests": {"value": len(picked), "limit": serve["sample_requests"]},
    }
    correct = (gap is not None and gap <= serve["gap_limit"]
               and n_tokens >= serve["sample_tokens"]
               and len(picked) >= serve["sample_requests"])
    return bool(correct), checks


# -- one run -------------------------------------------------------------------


@dataclasses.dataclass
class Served:
    """A served window and what the check and the readers need of it."""

    run: Run
    finished: Dict[str, tuple]
    params: object
    info: dict
    memory_peak: int
    compiles_setup: int
    compiles_window: int


def serve_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
               devices, t_start: float) -> Served:
    """Set-up and the window: weights from the seed, the scheduler, every
    tail length warmed, the cell's traffic before the window, the window.
    The scheduler and its KV pool are freed before this returns."""
    import jax

    info = device_info(devices)
    on_tpu = info["platform"] == "tpu"
    peaks = load_peaks(info["kind"]) if on_tpu else {}
    print(f"device: {json.dumps(info)}", file=sys.stderr)
    if on_tpu:
        enable_cache()

    from repro.core.runtime import Runtime
    from repro.serve.scheduler import Request

    prog = cell.program
    params = cell.reference.make_weights(prog, seed)
    jax.block_until_ready(params)
    s = cell.serve
    pre = s["pre_seconds"]
    items = traffic_gen.schedule(
        cell.traffic, rate=s["rate"], pre=pre, seconds=seconds, seed=seed,
        vocab=prog["vocab_size"],
    )
    compiles: list = []
    _listen_compiles(compiles)
    with Runtime("jaxdev") as rt:
        sched = build_scheduler(cell, params, rt)
        warm(sched, Request, cell.traffic, prog["vocab_size"], seed)
        driver = Driver(sched, Request, items)
        t_open = clock() + pre
        t_close = t_open + seconds
        driver.run(t_open, t_open)
        setup_s = clock() - t_start
        n_setup = len(compiles)
        prefix_open = dict(sched.prefix.stats())
        tracer = _Tracer(trace, t_open, rt.tracer)
        driver.run(t_open, t_close, hook=tracer.hook)
        tracer.stop()
        prefix_close = dict(sched.prefix.stats())
        memory_peak = peak_bytes(devices)
        n_window = len(compiles) - n_setup
        run = Run(cell=cell, peaks=peaks, setup_s=setup_s, t_open=t_open,
                  t_close=t_close, requests=driver.requests, steps=driver.steps,
                  admits=driver.admits, prefix_open=prefix_open,
                  prefix_close=prefix_close, trace=tracer.summary(),
                  trace_span=tracer.span, spans=tracer.spans,
                  spans_dropped=tracer.dropped)
        finished = driver.finished
        del sched, driver
    gc.collect()
    return Served(run, finished, params, info, memory_peak, n_setup, n_window)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             metrics: List[dict], devices, t_start: float, emit=print,
             control: Optional[str] = None) -> int:
    """One run of one cell, as the benchmark command makes it: set-up, the
    window, the check. Prints the result line last on stdout. With
    `control` (a key of the reference's `QUANTIZERS`), the reference at
    that lower precision is judged in the program's place, by the same
    decision."""
    served = serve_cell(cell, seed, seconds, trace, devices=devices, t_start=t_start)
    run, finished, info = served.run, served.finished, served.info
    memory_peak, n_setup, n_window = served.memory_peak, served.compiles_setup, served.compiles_window
    t_open, t_close, s = run.t_open, run.t_close, cell.serve
    values = {}
    for m in metrics:
        v = reader(m["name"])(run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    attempted = run.in_window()
    print(f"window: {len(attempted)} requests due, {len(run.steps)} steps, compiles "
          f"in set-up {n_setup}, compiles in window {n_window}, peak_bytes_in_use "
          f"{memory_peak}, prefix cache {run.prefix_close}", file=sys.stderr)
    waits = [(r.first or t_close) - r.due for r in attempted]
    if waits:
        print(f"ttft_ms p50 {np.median(waits) * 1e3:.1f}, requests without a first "
              f"token at the close {sum(r.first is None for r in attempted)}", file=sys.stderr)

    picked = sample(finished, t_open, t_close, seed, s["sample_tokens"],
                    s["sample_requests"])
    judged = control or "program"
    gaps = compare(cell, served.params, picked, controls=(control,) if control else ())
    correct, checks = judge(s, gaps.get(judged), picked)
    device = {"platform": info["platform"], "kind": info["kind"],
              "count": info["count"], "memory_peak_bytes": memory_peak}
    if trace and run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
    line = {"correct": correct, "attempted": len(attempted), "failed": 0,
            "metrics": values, "device": device}
    if trace and run.trace:
        line["breakdown"] = run.trace["breakdown"]
    line["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: {c['value']} limit {c['limit']}"
              f" ({'at most' if k == 'logit_gap' else 'at least'})", file=sys.stderr)
    emit(json.dumps(line))
    return 0


def _listen_compiles(sink: list) -> None:
    import jax

    def listener(event: str, duration: float, **_):
        if "backend_compile" in event:
            sink.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listener)


class _Tracer:
    """Profiles the first TRACE_SECONDS of the window (traced runs only),
    with the program's tracer (`repro.core.tracing.Tracer`) on for as long
    as the profiler runs."""

    def __init__(self, on: bool, t_open: float, program):
        self.on = on
        self.t_open = t_open
        self.program = program
        self.state = "idle"
        self.span = None
        self.spans: list = []
        self.dropped = 0
        self.dir = OUT_DIR / "trace"

    def hook(self, now: float) -> None:
        import jax

        if not self.on:
            return
        if self.state == "idle" and now >= self.t_open:
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(str(self.dir))
            self.program.enable()
            self.t0 = clock()
            self.state = "on"
        elif self.state == "on" and now - self.t0 >= TRACE_SECONDS:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state != "on":
            return
        t1 = clock()
        self.program.disable()
        jax.profiler.stop_trace()
        self.span = (self.t0, t1)
        self.state = "done"
        spans, self.dropped = self.program.drain()
        if self.dropped:
            print(f"the program's tracer dropped {self.dropped} spans: the span "
                  "readers read nothing", file=sys.stderr)
            return
        a, b = self.t0 * 1e9, t1 * 1e9
        self.spans = [s for s in spans if a <= s.t0_ns and s.t1_ns <= b]

    def summary(self) -> Optional[dict]:
        if self.state != "done":
            return None
        import program_spans

        try:
            return program_spans.summarize(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
