"""The chip benchmark's run and its correctness check, on the CPU at a size
a test run holds: the harness drives the program's paged prefix-cached
scheduler through set-up, traffic and the window, then compares the served
tokens with the float32 reference. A sound run is correct; the float8
control, judged in the program's place, is not; each fault planted
under the timed path (a token altered where it is produced, a decode
interval that returns the KV state unchanged) turns `correct` false; and
under documents asked again the radix cache hits as the asks predict. A
traced run hands the program's spans to the metric readers, an untraced one
never turns the program's tracer on, and a configuration brings a reader of
its own spans as a new file."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import harness  # noqa: E402

#: the limit at this size, between the bfloat16 program's widest gap
#: (0.036 at most over 12 runs of 12 sampled requests) and the float8
#: control's (0.087 at least over the same runs)
LIMIT = 0.06

#: light enough that a busy CPU finishes a dozen requests in the window;
#: outputs span two decode intervals, so a dropped interval shows
CHAT = {"prompt_lengths": [20, 37, 50],
        "output": {"dist": "lognormal", "median": 12, "sigma": 0.3, "min": 9, "max": 16}}
SECONDS = 6.0


def tiny_cell(gated: bool) -> harness.Cell:
    """granite-like (one KV head, GELU) or grouped-query with SwiGLU, the
    reference's other block."""
    prog = dict(family="dense", num_layers=2, d_model=128, num_heads=4,
                num_kv_heads=2 if gated else 1, d_ff=256, vocab_size=1024,
                max_seq_len=512, act="silu" if gated else "gelu", rope_theta=10000.0,
                norm_eps=1e-6, tie_embeddings=False, param_dtype="bfloat16",
                compute_dtype="bfloat16")
    return harness.Cell(
        name="tiny", chips=1, config={"name": "tiny", "program": prog},
        traffic=CHAT,
        serve={"rate": 4.0, "max_batch": 4, "max_len": 96, "sample_tokens": 120,
               "sample_requests": 12, "gap_limit": LIMIT, "pre_seconds": 1.0})


CELLS = {"mqa-gelu": False, "gqa-swiglu": True}


def run_line(cell, seed=7, control=None):
    import jax

    lines = []
    metrics = harness.metric_names("granite-20b.chat", False)
    harness.run_cell(cell, seed, SECONDS, False, metrics=metrics, devices=jax.devices(),
                     t_start=time.perf_counter(), emit=lines.append, control=control)
    return json.loads(lines[-1])


@pytest.mark.parametrize("name", sorted(CELLS))
def test_sound_run_is_correct_and_the_control_is_not(name):
    import jax

    cell = tiny_cell(CELLS[name])
    served = harness.serve_cell(cell, 11, SECONDS, False, devices=jax.devices(),
                                t_start=time.perf_counter())
    run = served.run
    picked = harness.sample(served.finished, run.t_open, run.t_close, 11, 120, 12)
    assert sum(len(f.tokens) for f in picked) >= 120 and len(picked) >= 12
    gaps = harness.compare(cell, served.params, picked, controls=("fp8",))
    assert gaps["program"] <= LIMIT < gaps["fp8"]
    assert harness.reader("output_tok_s")(run) > 0
    assert harness.reader("prefix_hit_share")(run) == 0


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_control_in_the_programs_place_is_not_correct(name):
    line = run_line(tiny_cell(CELLS[name]), seed=13, control="fp8")
    assert line["correct"] is False
    assert line["checks"]["logit_gap"]["value"] > LIMIT
    assert line["checks"]["sampled_tokens"]["value"] >= 120


def test_run_line_names_its_checks_last():
    line = run_line(tiny_cell(False))
    assert line["correct"] is True
    assert list(line)[-1] == "checks"
    assert line["checks"]["logit_gap"]["limit"] == LIMIT
    assert set(line["metrics"]) == {"output_tok_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert line["device"]["platform"] == "cpu" and line["attempted"] > 0


def run_keeping(monkeypatch, tmp_path, cell, seed, trace, metrics, seconds=SECONDS):
    """`run_cell` as the benchmark command makes it, keeping what it served;
    traces go under `tmp_path`."""
    import jax

    kept = {}
    serve_cell = harness.serve_cell

    def serve_and_keep(*args, **kw):
        kept["served"] = serve_cell(*args, **kw)
        return kept["served"]

    monkeypatch.setattr(harness, "serve_cell", serve_and_keep)
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)
    lines = []
    harness.run_cell(cell, seed, seconds, trace, metrics=metrics, devices=jax.devices(),
                     t_start=time.perf_counter(), emit=lines.append)
    return json.loads(lines[-1]), kept["served"]


SPAN_METRICS = {"prefill_ms.p50", "admit_host_ms.p50", "step_host_ms.p50"}


def test_a_traced_run_hands_the_programs_spans_to_the_readers(monkeypatch, tmp_path):
    """The program's spans inside the profiled part become `Run.spans`, the
    span readers read them, and idle gaps are named by program span (a CPU
    trace has no device plane: ops that leave the device idle for 1 us at
    the middle of each benchmark span stand in for the device's)."""
    import program_spans
    import trace_reduce

    extract = trace_reduce.extract

    def with_device_ops(path):
        _, host = extract(path)
        mids = [(a + b) // 2 for a, b, _ in sorted(host)]
        edges = ([min(a for a, _, _ in host) - 1000] + mids
                 + [max(b for _, b, _ in host) + 1000])
        ops = [(x + 500, y - 500, "op") for x, y in zip(edges, edges[1:])]
        return {"/device:TPU:0": ops}, host

    monkeypatch.setattr(trace_reduce, "extract", with_device_ops)
    metrics = harness.metric_names("granite-20b.chat", True)
    line, served = run_keeping(monkeypatch, tmp_path, tiny_cell(False), 19, True, metrics)
    run = served.run
    a, b = run.trace_span
    assert run.spans and run.spans_dropped == 0
    assert all(a * 1e9 <= s.t0_ns <= s.t1_ns <= b * 1e9 for s in run.spans)
    assert {"serve.admit", "serve.admit.prefill", "serve.step",
            "serve.step.interval"} <= {s.name for s in run.spans}
    for m in SPAN_METRICS:
        assert line["metrics"][m]["value"] > 0
    gaps = [name for name, _ in line["breakdown"]["idle_gaps"]]
    assert any(n.partition("/")[2].startswith(program_spans.PREFIXES) for n in gaps)
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["correct"] is True


def test_an_untraced_run_never_turns_the_programs_tracer_on(monkeypatch, tmp_path):
    from repro.core.tracing import Tracer

    enabled = []
    seen = set()
    span = Tracer.span

    def watched(self, name, **attrs):
        seen.add(self.enabled)
        return span(self, name, **attrs)

    monkeypatch.setattr(Tracer, "enable", lambda self, *a, **kw: enabled.append(a))
    monkeypatch.setattr(Tracer, "span", watched)
    metrics = harness.metric_names("granite-20b.chat", False)
    line, served = run_keeping(monkeypatch, tmp_path, tiny_cell(False), 23, False, metrics)
    assert enabled == [] and seen == {False}
    assert served.run.spans == [] and served.run.spans_dropped == 0
    assert served.run.trace is None and served.run.trace_span is None
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(line["metrics"]) == {m["name"] for m in metrics}
    assert line["correct"] is True


def test_a_ring_that_drops_spans_leaves_the_span_readers_nothing(monkeypatch, tmp_path,
                                                                 capsys):
    from repro.core.tracing import Tracer

    enable = Tracer.enable
    monkeypatch.setattr(Tracer, "enable", lambda self, capacity=0: enable(self, 4))
    metrics = harness.metric_names("granite-20b.chat", True)
    line, served = run_keeping(monkeypatch, tmp_path, tiny_cell(False), 29, True, metrics,
                               seconds=3.0)
    run = served.run
    assert run.spans == [] and run.spans_dropped > 0
    assert not SPAN_METRICS & set(line["metrics"])
    assert f"dropped {run.spans_dropped} spans" in capsys.readouterr().err


def _alter_tokens(self, table, _orig=None):
    out, done, pos = _orig(self, table)
    return np.where(out >= 0, (out + 1) % self.model.cfg.vocab_size, out), done, pos


def _keep_state(self, table, _orig=None):
    pools = self.kv.pools
    result = _orig(self, table)
    self.kv.pools = pools  # the interval's K/V writes are dropped
    return result


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_fault_under_the_timed_path_is_not_correct(name, fault, monkeypatch):
    from repro.serve.batching import PagedSlotDecoder

    orig = PagedSlotDecoder.run_interval
    broken = {"token_altered": _alter_tokens, "state_unchanged": _keep_state}[fault]
    monkeypatch.setattr(PagedSlotDecoder, "run_interval",
                        lambda self, table: broken(self, table, _orig=orig))
    line = run_line(tiny_cell(CELLS[name]))
    assert line["correct"] is False
    assert line["checks"]["logit_gap"]["value"] > LIMIT


#: documents asked three or four times (one not a whole number of 16-token
#: pages), questions of a page or more; no traffic before the window, and a
#: `max_len` whose default pool (the slots' worst case, 4 x 32 + 1 pages)
#: holds every page the run writes (10 documents of 2 full pages, 35 asks
#: of at most 2 more, 4 slots' reservations of at most 5), so that the ask
#: counts alone decide which asks hit
DOC_QA = {"shared": {"documents": [32, 41], "asks": [3, 4], "questions": [16, 21]},
          "output": {"dist": "uniform", "min": 9, "max": 16}}


def test_shared_documents_hit_as_their_asks_predict(monkeypatch, tmp_path, capsys):
    """A tiny cell under document traffic, through `run_cell`: correct, no
    compile in the window, and the radix cache's hit share is what the ask
    counts predict: an ask hits its whole document where an earlier ask of
    it has finished (asks - 1 of asks, where they come one after another)."""
    import traffic_gen

    cell = tiny_cell(False)
    cell.traffic = DOC_QA
    # fewer requests finish in the window than under chat: prompts are longer
    cell.serve = dict(cell.serve, pre_seconds=0.0, max_len=512, sample_tokens=80,
                      sample_requests=8)
    metrics = [{"name": "prefix_hit_share", "unit": "%"},
               {"name": "output_tok_s", "unit": "tokens/s"}]
    line, served = run_keeping(monkeypatch, tmp_path, cell, 17, False, metrics)
    assert line["correct"] is True
    assert served.compiles_window == 0
    assert "compiles in window 0," in capsys.readouterr().err
    run = served.run
    assert run.prefix_close["evictions"] == run.prefix_open["evictions"]

    items = traffic_gen.schedule(DOC_QA, rate=cell.serve["rate"], pre=0.0, seconds=SECONDS,
                                 seed=17, vocab=cell.program["vocab_size"])
    prompts = {it.rid: it.prompt for it in items}
    asks = {}
    for rid, p in prompts.items():
        asks.setdefault(p[0], []).append(rid)
    doc_len = {}  # an ask's document: the prefix all asks of it share
    for rids in asks.values():
        d = min(next(i for i, (x, y) in enumerate(zip(prompts[rids[0]], prompts[r])) if x != y)
                for r in rids[1:])
        doc_len.update({r: d for r in rids})
    admitted = sorted((r.admit_start, rid) for rid, r in run.requests.items()
                      if r.admit_start is not None)
    hit = n_hits = queried = 0
    for t, rid in admitted:
        queried += len(prompts[rid])
        first = prompts[rid][0]
        if any(run.requests[o].finished is not None and run.requests[o].finished <= t
               for o in asks[first] if o != rid):
            hit += doc_len[rid]
            n_hits += 1
    assert n_hits >= 3
    page = 16
    assert line["metrics"]["prefix_hit_share"]["value"] == pytest.approx(
        100.0 * hit / queried, abs=100.0 * page * n_hits / queried)


def _bench_cmd(cwd, workload="granite-20b.chat"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", workload,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_means_no_result():
    r = _bench_cmd(ROOT)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _bench_cmd(tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""


#: a configuration's reader of its own program's span attribute, as a
#: configuration's PR would add it: pages the paged kernel walks per tick
SPAN_READER = '''"""Mean over the traced window's decode intervals of the pages walked."""


def read(run):
    vals = [s.attrs["kv_pages_live"] for s in run.spans
            if s.name == "serve.step.interval" and "kv_pages_live" in s.attrs]
    return sum(vals) / len(vals) if vals else None
'''

#: what the benchmark command does after its look for a chip
DRIVE = """
import sys, time
t_start = time.perf_counter()
sys.path[:0] = sys.argv[1:3]
import jax
import harness
cell = harness.load_cell("tiny.chat")
metrics = harness.metric_names("tiny.chat", True)
sys.exit(harness.run_cell(cell, 3, 3.0, True, metrics=metrics, devices=jax.devices()[:1],
                          t_start=t_start))
"""


def test_a_configuration_brings_a_reader_of_its_own_spans_as_a_new_file(tmp_path):
    """A copy of the benchmark gains a configuration, a traffic mix, a cell
    and a reader of a span attribute, as new files and new entries of
    `BENCHMARK.json`; a traced run of the new cell reports the new metric,
    and no file that was there is changed."""
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, chip, ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    had = sorted(p.relative_to(chip) for p in chip.rglob("*") if p.is_file())
    cell = tiny_cell(False)
    serve = dict(cell.serve, pre_seconds=0.5)
    for path, text in [("configs/tiny.json", json.dumps(cell.config)),
                       ("traffic/tiny-chat.json", json.dumps(cell.traffic)),
                       ("cells/tiny.chat.json", json.dumps(serve)),
                       ("metrics/kv_pages_live.mean.py", SPAN_READER)]:
        (chip / path).write_text(text)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "a test's own", "file":
                            "benchmarks/chip/configs/tiny.json", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.chat", "config": "tiny", "traffic": "tiny-chat",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "kv_pages_live.mean", "unit": "pages", "better": "lower",
                              "source": "program_counter", "layer": "kernels",
                              "moves": "tpot_p95_ms", "workloads": ["tiny.chat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    r = subprocess.run([sys.executable, "-c", DRIVE, str(chip), str(ROOT / "src")],
                       cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert set(line["metrics"]) == {"kv_pages_live.mean"}
    assert line["metrics"]["kv_pages_live.mean"]["value"] > 0
    for rel in had:
        assert (chip / rel).read_bytes() == (HERE / rel).read_bytes(), rel

