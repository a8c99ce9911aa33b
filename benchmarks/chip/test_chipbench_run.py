"""The chip benchmark's run and its correctness check, on the CPU at a size
a test run holds: the harness drives the program's paged prefix-cached
scheduler through set-up, traffic and the window, then compares the served
tokens with the float32 reference. A sound run is correct; the float8
control, judged in the program's place, is not; each fault planted
under the timed path (a token altered where it is produced, a decode
interval that returns the KV state unchanged) turns `correct` false; and
under documents asked again the radix cache hits as the asks predict."""
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


def test_shared_documents_hit_as_their_asks_predict(monkeypatch, capsys):
    """A tiny cell under document traffic, through `run_cell`: correct, no
    compile in the window, and the radix cache's hit share is what the ask
    counts predict: an ask hits its whole document where an earlier ask of
    it has finished (asks - 1 of asks, where they come one after another)."""
    import jax

    import traffic_gen

    cell = tiny_cell(False)
    cell.traffic = DOC_QA
    # fewer requests finish in the window than under chat: prompts are longer
    cell.serve = dict(cell.serve, pre_seconds=0.0, max_len=512, sample_tokens=80,
                      sample_requests=8)
    kept = {}

    def serve_and_keep(*args, **kw):
        kept["served"] = serve_cell(*args, **kw)
        return kept["served"]

    serve_cell = harness.serve_cell
    monkeypatch.setattr(harness, "serve_cell", serve_and_keep)
    lines = []
    metrics = [{"name": "prefix_hit_share", "unit": "%"},
               {"name": "output_tok_s", "unit": "tokens/s"}]
    harness.run_cell(cell, 17, SECONDS, False, metrics=metrics, devices=jax.devices(),
                     t_start=time.perf_counter(), emit=lines.append)
    line = json.loads(lines[-1])
    assert line["correct"] is True
    served = kept["served"]
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
