"""Idle gaps named by program span and the span readers, on the CPU: by hand
on made-up spans, and on the records of a tiny served window."""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import program_spans  # noqa: E402
import trace_reduce  # noqa: E402
from repro.core.tracing import Span  # noqa: E402

MS = 1_000_000  # ns

OPS = {"/device:TPU:0": [(10 * MS, 30 * MS, "fusion"), (25 * MS, 40 * MS, "paged_decode_attention/k"),
                         (70 * MS, 90 * MS, "fusion"), (95 * MS, 100 * MS, "fusion"),
                         (120 * MS, 125 * MS, "prefill")]}
HOST = [(0, 50 * MS, "try_admit"), (50 * MS, 100 * MS, "step"), (112 * MS, 130 * MS, "try_admit")]


def test_without_program_spans_the_reduction_is_unchanged():
    assert program_spans.label(HOST, []) == sorted(HOST)
    before = trace_reduce.reduce(OPS, HOST)
    after = trace_reduce.reduce(OPS, program_spans.label(HOST, []))
    assert json.dumps(after) == json.dumps(before)


def test_a_gap_is_named_by_the_innermost_program_span():
    program = [
        (1 * MS, 49 * MS, "serve.admit"),
        (2 * MS, 48 * MS, "serve.admit.prefill"),
        (3 * MS, 47 * MS, "hicr.run:prefix_prefill"),
        (52 * MS, 99 * MS, "serve.step"),
        (53 * MS, 68 * MS, "serve.step.interval"),
        (68 * MS, 98 * MS, "serve.step.harvest"),
        (113 * MS, 118 * MS, "serve.admit"),
        (200 * MS, 210 * MS, "serve.step"),  # outside every benchmark span
    ]
    plain = trace_reduce.reduce(OPS, HOST)
    named = trace_reduce.reduce(OPS, program_spans.label(HOST, program))
    for key in ("window_s", "busy_s", "op_s"):
        assert named[key] == plain[key]
    assert named["breakdown"]["device_ops"] == plain["breakdown"]["device_ops"]
    # 0-10 (midpoint 5, in the prefill's unit), 40-70 (55, the interval),
    # 90-95 (92.5, the harvest), 100-120 (110, between the benchmark's
    # spans), 125-130 (127.5, in try_admit after its serve.admit closed)
    assert named["breakdown"]["idle_gaps"] == [
        ["step/serve.step.interval", pytest.approx(0.030)],
        ["driver", pytest.approx(0.020)],
        ["try_admit/hicr.run:prefix_prefill", pytest.approx(0.010)],
        ["try_admit", pytest.approx(0.005)],
        ["step/serve.step.harvest", pytest.approx(0.005)],
    ]


def test_label_cuts_disjoint_pieces_that_cover_each_benchmark_span():
    program = [(10, 90, "serve.step"), (20, 40, "serve.step.grow"), (40, 80, "serve.step.interval"),
               (45, 70, "hicr.run:fused_decode_x8"), (50, 70, "hicr.wait:fused_decode_x8")]
    pieces = program_spans.label([(0, 100, "step")], program)
    assert pieces == [(0, 10, "step"), (10, 20, "step/serve.step"),
                      (20, 40, "step/serve.step.grow"), (40, 45, "step/serve.step.interval"),
                      (45, 50, "step/hicr.run:fused_decode_x8"),
                      (50, 70, "step/hicr.wait:fused_decode_x8"),
                      (70, 80, "step/serve.step.interval"), (80, 90, "step/serve.step"),
                      (90, 100, "step")]


# -- readers ----------------------------------------------------------------------


def _admit(i, t0, outcome, prefill_ms, wait_ms, host_ms):
    """An admission of `host_ms` host time around a prefill that waits
    `wait_ms` on the device; ids 10*i.."""
    b = i * 10
    t1 = t0 + (host_ms + wait_ms) * MS
    pre0 = t0 + MS
    pre1 = pre0 + prefill_ms * MS
    return [
        Span(b + 3, b + 2, "hicr.wait:prefix_prefill", pre0, pre0 + wait_ms * MS, {}),
        Span(b + 2, b + 1, "hicr.run:prefix_prefill", pre0, pre0 + wait_ms * MS, {}),
        Span(b + 1, b, "serve.admit.prefill", pre0, pre1, {"tail_len": 7}),
        Span(b, None, "serve.admit", t0, t1, {"outcome": outcome, "rid": f"r{i}"}),
    ]


def _step(i, t0, host_ms, wait_ms, slots, tokens):
    b = 1000 + i * 10
    w0 = t0 + MS
    return [
        Span(b + 3, b + 2, "hicr.wait:fused_decode_x8", w0, w0 + wait_ms * MS, {}),
        Span(b + 2, b + 1, "hicr.run:fused_decode_x8", w0, w0 + wait_ms * MS, {}),
        Span(b + 1, b, "serve.step.interval", w0, w0 + wait_ms * MS, {}),
        Span(b, None, "serve.step", t0, t0 + (host_ms + wait_ms) * MS,
             {"slots": slots, "tokens": tokens, "finished": 0}),
    ]


SPANS = (_admit(1, 0, "seated", 80, 78, 3) + _admit(2, 200 * MS, "seated", 90, 88, 5)
         + _admit(3, 400 * MS, "seated", 70, 68, 3) + _admit(4, 600 * MS, "finished", 500, 498, 9)
         + [Span(60, None, "serve.admit", 700 * MS, 701 * MS, {"outcome": "no_slot"})]
         + _step(1, 800 * MS, 4, 400, 384, 300) + _step(2, 1300 * MS, 6, 420, 384, 200)
         + [Span(99, None, "xla.compile", 5, 6, {})])


def test_readers_by_hand():
    assert program_spans.prefill_ms_p50(SPANS) == pytest.approx(80)
    assert program_spans.admit_host_ms_p50(SPANS) == pytest.approx(3)
    assert program_spans.step_host_ms_p50(SPANS) == pytest.approx(5)
    assert program_spans.slot_occupancy(SPANS) == pytest.approx(100 * 500 / 768)


@pytest.mark.parametrize("reader", ["prefill_ms_p50", "admit_host_ms_p50",
                                    "step_host_ms_p50", "slot_occupancy"])
def test_readers_with_nothing_to_read_give_none(reader):
    assert getattr(program_spans, reader)([]) is None


# -- on a served window ----------------------------------------------------------


def test_a_served_window_records_spans_the_readers_and_profiler_read(tmp_path):
    """A tiny cell through the benchmark's driver with the tracer on: the
    readers give numbers, and the profiler's host plane holds the spans."""
    import jax

    import harness
    import refmodel
    import traffic_gen
    from repro.core.runtime import Runtime
    from repro.serve.scheduler import Request

    prog = dict(family="dense", num_layers=2, d_model=64, num_heads=4, num_kv_heads=1,
                d_ff=128, vocab_size=512, max_seq_len=256, act="gelu", rope_theta=10000.0,
                norm_eps=1e-6, tie_embeddings=False, param_dtype="bfloat16",
                compute_dtype="bfloat16")
    traffic = {"prompt_lengths": [12, 20],
               "output": {"dist": "uniform", "min": 6, "max": 12}}
    cell = harness.Cell(name="tiny", chips=1, config={"name": "tiny", "program": prog},
                        traffic=traffic, serve={"max_batch": 3, "max_len": 48})
    params = refmodel.make_weights(prog, 5)
    items = traffic_gen.schedule(traffic, rate=6.0, span=3.0, pre=0.0, seed=5,
                                 vocab=prog["vocab_size"])
    with Runtime("jaxdev") as rt:
        sched = harness.build_scheduler(cell, params, rt)
        harness.warm(sched, Request, traffic, prog["vocab_size"], 5)
        driver = harness.Driver(sched, Request, items)
        rt.tracer.enable()
        jax.profiler.start_trace(str(tmp_path))
        try:
            t_open = time.perf_counter()
            driver.run(t_open, t_open + 2.0)
        finally:
            jax.profiler.stop_trace()
        spans, dropped = rt.tracer.drain()
    assert dropped == 0 and spans
    for reader in ("prefill_ms_p50", "admit_host_ms_p50", "step_host_ms_p50"):
        assert getattr(program_spans, reader)(spans) > 0
    assert 0 < program_spans.slot_occupancy(spans) <= 100
    on_plane = {n for _, _, n in program_spans.extract_program(
        trace_reduce.find_xplane(tmp_path))}
    assert {"serve.admit", "serve.admit.prefill", "serve.step", "serve.step.harvest",
            "hicr.wait:fused_decode_x8"} <= on_plane
