"""The chip benchmark's arithmetic, on the CPU: trace reduction, operation
and byte counts, traffic generation, and the files that define the cells."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import harness  # noqa: E402
import trace_reduce  # noqa: E402
import traffic_gen  # noqa: E402
import work  # noqa: E402

MS = 1_000_000  # ns


# -- trace reduction ----------------------------------------------------------


def test_union_merges_overlaps_and_keeps_gaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_reduce_busy_idle_ops_and_named_gaps():
    # window 0..100 ms from the host spans; device busy 10-30, 25-40, 70-90
    ops = {"/device:TPU:0": [(10 * MS, 30 * MS, "fusion"), (25 * MS, 40 * MS, "paged_decode_attention/k"),
                             (70 * MS, 90 * MS, "fusion")]}
    host = [(0, 50 * MS, "try_admit"), (50 * MS, 100 * MS, "step")]
    r = trace_reduce.reduce(ops, host)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.050)
    assert r["op_s"]["fusion"] == pytest.approx(0.040)
    assert r["op_s"]["paged_decode_attention/k"] == pytest.approx(0.015)
    gaps = r["breakdown"]["idle_gaps"]
    # gaps: 0-10 (try_admit), 40-70 (midpoint 55: step), 90-100 (step)
    assert gaps[0] == ["step", pytest.approx(0.030)]
    assert sorted(g[1] for g in gaps) == pytest.approx([0.010, 0.010, 0.030])
    assert r["breakdown"]["device_ops"][0] == ["fusion", pytest.approx(0.040)]


def test_reduce_clips_to_window_and_averages_devices():
    ops = {"/device:TPU:0": [(-10 * MS, 10 * MS, "a")], "/device:TPU:1": [(0, 20 * MS, "a")]}
    host = [(0, 20 * MS, "step")]
    r = trace_reduce.reduce(ops, host)
    assert r["busy_s"] == pytest.approx((0.010 + 0.020) / 2)
    assert r["op_s"]["a"] == pytest.approx(0.015)


def test_reduce_with_nothing_to_read_is_empty():
    assert trace_reduce.reduce({}, [(0, 1, "step")]) == {}
    assert trace_reduce.reduce({"/device:TPU:0": [(0, 1, "a")]}, []) == {}


def test_op_key_prefers_hlo_path():
    assert trace_reduce.op_key("fusion.12", {"tf_op": "jit(f)/dot"}) == "jit(f)/dot"
    assert trace_reduce.op_key("fusion.12", {}) == "fusion"


# -- operation and byte counts --------------------------------------------------

SMALL = dict(num_layers=2, d_model=8, num_heads=4, num_kv_heads=2, d_ff=16,
             vocab_size=10, act="gelu")


def test_matmul_params_by_hand():
    # per layer: q 8*4*2 + k,v 2*8*2*2 + o 4*2*8 = 64+64+64 = 192; mlp 2*8*16 = 256
    assert work.matmul_params(SMALL) == 2 * (192 + 256) + 8 * 10
    gated = dict(SMALL, act="silu")
    assert work.matmul_params(gated) == 2 * (192 + 384) + 8 * 10


def test_attention_work_by_hand():
    # per layer per token at ctx 5: 4 * H * hd * ctx = 4*4*2*5 = 160 flops
    assert work.attn_flops(SMALL, 5) == 2 * 160
    # bytes: K and V of 5 positions * KV 2 * hd 2 * 2 B = 80, q and o 2*4*2*2 = 32
    assert work.attn_bytes(SMALL, 5) == 2 * (80 + 32)
    assert work.decode_flops(SMALL, 5) == 2 * work.matmul_params(SMALL) + 2 * 160


def test_contexts_and_least_time():
    assert list(work.contexts([(3, 2), (10, 1)])) == [3, 4, 10]
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(1000, 50, peaks) == 10.0
    assert work.least_time(100, 50, peaks) == 5.0


# -- traffic --------------------------------------------------------------------


#: a mix with on/off bursts: 5 s at 2.5 times the mean rate, 10 s at a quarter
BURST = {"prompt_lengths": [8, 16, 24], "rate_profile": [[5.0, 2.5], [10.0, 0.25]],
         "output": {"dist": "uniform", "min": 4, "max": 12}}
MIXES = {"chat": lambda: traffic_gen.load("chat"), "burst": lambda: BURST}


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
@pytest.mark.parametrize("name", sorted(MIXES))
def test_schedule_is_fixed_by_the_seed(name, seed):
    t = MIXES[name]()
    a = traffic_gen.schedule(t, rate=3.0, span=60, pre=5, seed=seed, vocab=49152)
    b = traffic_gen.schedule(t, rate=3.0, span=60, pre=5, seed=seed, vocab=49152)
    c = traffic_gen.schedule(t, rate=3.0, span=60, pre=5, seed=seed + 1, vocab=49152)
    assert [(i.due, i.prompt, i.max_new) for i in a] == [(i.due, i.prompt, i.max_new) for i in b]
    assert [i.prompt for i in a] != [i.prompt for i in c]
    # another seed: the same sizes, in another order
    assert sorted(len(i.prompt) for i in a) == sorted(len(i.prompt) for i in c)
    assert [len(i.prompt) for i in a] != [len(i.prompt) for i in c]
    assert sorted(i.max_new for i in a) == sorted(i.max_new for i in c)
    assert a[0].due == pytest.approx(-5.0)
    assert len(a) == 180 and all(x.due < y.due for x, y in zip(a, a[1:]))


@pytest.mark.parametrize("name", sorted(MIXES))
def test_every_prompt_length_is_a_warmed_tail(name):
    t = MIXES[name]()
    tails = set(traffic_gen.tail_lengths(t))
    items = traffic_gen.schedule(t, rate=5.0, span=60, pre=10, seed=3, vocab=49152)
    assert {len(i.prompt) for i in items} == tails
    out = t["output"]
    assert all(out["min"] <= i.max_new <= out["max"] for i in items)
    firsts = [i.prompt[0] for i in items]
    assert len(firsts) == len(set(firsts))  # nothing shared by accident
    warmed = [len(it.prompt) for it in traffic_gen.warm_items(t, 49152, 3)]
    assert sorted(warmed) == sorted(tails)


def test_gaps_are_poisson_in_a_seeded_order():
    """The same exponential gaps on every seed, in an order with no pattern:
    the gaps' ranks do not drift along the schedule, and runs of short gaps
    occur as often as in an i.i.d. stream."""
    import numpy as np

    n, rate, short, runs = 2000, 4.0, 0, []
    for seed in range(4):
        items = traffic_gen.schedule({"prompt_lengths": [8], "output": BURST["output"]},
                                     rate=rate, span=n / rate, pre=0, seed=seed, vocab=4096)
        gaps = np.diff([i.due for i in items])
        runs.append(gaps)
        assert np.mean(gaps) == pytest.approx(1 / rate, rel=0.02)
        assert abs(np.corrcoef(np.arange(len(gaps)), gaps)[0, 1]) < 0.1
        # three gaps in a row below the median: (1/2)^3 of the triples
        below = gaps < np.log(2) / rate
        short += int(np.sum(below[:-2] & below[1:-1] & below[2:]))
    # each seed leaves out one gap of the set: the one before the first due
    assert len(np.intersect1d(np.round(runs[0], 9), np.round(runs[1], 9))) >= n - 3
    assert short / (4 * (n - 3)) == pytest.approx(1 / 8, abs=0.02)


def test_rescale_is_plain_scaling_for_a_constant_rate():
    import numpy as np

    s = np.array([0.0, 1.0, 2.5, 7.0])
    assert traffic_gen.rescale(s, 2.0, [[1.0, 1.0]]) == pytest.approx(s / 2.0)


def test_burst_profile_packs_arrivals_into_the_on_phase():
    import numpy as np

    items = traffic_gen.schedule(BURST, rate=2.0, span=600, pre=0, seed=4, vocab=4096)
    dues = np.array([i.due for i in items])
    on = (dues % 15.0) < 5.0
    # mean rate kept; the on third of each period holds 2.5 / (2.5 + 0.5) of it
    assert dues[-1] == pytest.approx(600, rel=0.05)
    assert on.mean() == pytest.approx(2.5 * 5 / (2.5 * 5 + 0.25 * 10), abs=0.03)


def test_output_quantiles_follow_the_distribution():
    lens = traffic_gen.quantiles({"dist": "uniform", "min": 32, "max": 192}, 4)
    assert lens == [52, 92, 132, 172]
    ln = traffic_gen.quantiles({"dist": "lognormal", "median": 192, "sigma": 0.9,
                                "min": 16, "max": 768}, 101)
    assert ln[50] == 192 and min(ln) >= 16 and max(ln) <= 768


# -- the correctness decision ------------------------------------------------------

SERVE = {"gap_limit": 0.05, "sample_tokens": 10, "sample_requests": 2}


class _Fin:
    def __init__(self, n):
        self.tokens = [0] * n


@pytest.mark.parametrize("gap, lens, correct", [
    (0.01, [6, 6], True),
    (0.05, [6, 6], True),  # the limit itself passes
    (0.06, [6, 6], False),  # the gap over its limit
    (None, [], False),  # nothing finished to compare
    (0.01, [12], False),  # too few requests sampled
    (0.01, [4, 4], False),  # too few tokens sampled
])
def test_judge_holds_each_number_to_its_limit(gap, lens, correct):
    ok, checks = harness.judge(SERVE, gap, [_Fin(n) for n in lens])
    assert ok is correct
    assert checks["logit_gap"] == {"value": gap, "limit": 0.05}
    assert checks["sampled_tokens"]["value"] == sum(lens)


# -- the benchmark's own files ----------------------------------------------------


def test_every_cell_metric_and_config_has_its_file():
    spec = harness.bench()
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"], spec)
        assert cell.serve["rate"] > 0
        assert harness.metric_names(w["name"], False, spec)
        assert harness.metric_names(w["name"], True, spec)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.reader(m["name"]))
    for c in spec["configs"]:
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        for key in c["reduced"]:
            assert key in cfg and key in cfg["published"]


def test_unknown_device_kind_has_no_peaks():
    assert harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        harness.load_peaks("TPU v9 imaginary")
