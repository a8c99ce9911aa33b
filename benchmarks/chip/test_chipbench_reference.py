"""A configuration names its own reference module: a tiny configuration
written into a copy of the benchmark directory is served with that module's
weights, judged by its gaps and counted by its counts (it has none); one
that names a module with no file stops at `load_cell`; and no file of the
benchmark reaches the default reference but through a cell."""
from __future__ import annotations

import json
import re
import shutil
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import harness  # noqa: E402
import traffic_gen  # noqa: E402
import work  # noqa: E402

PROG = dict(family="dense", num_layers=2, d_model=128, num_heads=4, num_kv_heads=1,
            d_ff=256, vocab_size=1024, max_seq_len=512, act="gelu", rope_theta=10000.0,
            norm_eps=1e-6, tie_embeddings=False, param_dtype="bfloat16",
            compute_dtype="bfloat16")
TRAFFIC = {"prompt_lengths": [20, 37],
           "output": {"dist": "uniform", "min": 9, "max": 14}}
SERVE = {"rate": 4.0, "max_batch": 4, "max_len": 64, "pre_seconds": 1.0,
         "sample_tokens": 40, "sample_requests": 4, "gap_limit": 0.06}

#: the second reference: the dense one's block, with its calls recorded and
#: without work counts
ALTREF_TAIL = '''

CALLS = []
_make_weights, _served_gaps = make_weights, served_gaps
del matmul_params, attn_flops, attn_bytes, decode_flops


def make_weights(p, seed):
    CALLS.append(("make_weights", seed))
    return _make_weights(p, seed)


def served_gaps(p, params, prompt, served, **kw):
    gaps = _served_gaps(p, params, prompt, served, **kw)
    CALLS.append(("served_gaps", gaps["program"]))
    return gaps
'''


def bench_copy(tmp_path, monkeypatch, reference: str) -> dict:
    """The benchmark directory copied under `tmp_path` with a tiny cell whose
    configuration names `reference`, and the harness pointed at the copy."""
    chip = tmp_path / "benchmarks" / "chip"
    shutil.copytree(HERE, chip, ignore=shutil.ignore_patterns("__pycache__"))
    (chip / "altref.py").write_text((HERE / "refmodel.py").read_text() + ALTREF_TAIL)
    (chip / "configs" / "tiny.json").write_text(
        json.dumps({"name": "tiny", "reference": reference, "program": PROG}))
    (chip / "cells" / "tiny.chat.json").write_text(json.dumps(SERVE))
    (chip / "traffic" / "tiny.json").write_text(json.dumps(TRAFFIC))
    monkeypatch.setattr(harness, "HERE", chip)
    monkeypatch.setattr(traffic_gen, "HERE", chip)
    monkeypatch.setitem(sys.modules, f"reference_{reference}", None)
    return {"workloads": [{"name": "tiny.chat", "config": "tiny", "traffic": "tiny",
                           "chips": 1}]}


def test_a_named_reference_serves_judges_and_counts(tmp_path, monkeypatch):
    import jax

    spec = bench_copy(tmp_path, monkeypatch, "altref")
    cell = harness.load_cell("tiny.chat", spec)
    ref = cell.reference
    assert Path(ref.__file__) == harness.HERE / "altref.py"
    assert work.decode_flops(cell, 5) is None and work.attn_bytes(cell, 5) is None
    lines = []
    harness.run_cell(cell, 23, 4.0, False,
                     metrics=[{"name": "output_tok_s", "unit": "tokens/s"}],
                     devices=jax.devices(), t_start=time.perf_counter(), emit=lines.append)
    line = json.loads(lines[-1])
    assert ref.CALLS[0] == ("make_weights", 23)
    judged = [g for name, g in ref.CALLS if name == "served_gaps"]
    assert len(judged) == line["checks"]["sampled_requests"]["value"] >= 4
    assert line["checks"]["logit_gap"]["value"] == max(judged)
    assert line["correct"] is True


def test_a_reference_with_no_file_stops_at_load_cell(tmp_path, monkeypatch):
    spec = bench_copy(tmp_path, monkeypatch, "nosuchref")
    with pytest.raises(SystemExit, match=re.escape(str(harness.HERE / "nosuchref.py"))):
        harness.load_cell("tiny.chat", spec)


def test_the_default_reference_is_reached_only_through_a_cell():
    by_name = re.compile(r"^\s*(import|from)\s+refmodel\b", re.M)
    assert [p.name for p in sorted(HERE.rglob("*.py")) if by_name.search(p.read_text())] == []
