"""The Runtime's tracer (core/tracing.py) and the serve path's spans.

Off, a span is one shared no-op object: no record, no clock read, no
new object. On, spans nest per thread, a full ring drops its oldest
records and counts them, XLA compiles inside a span become its
``xla.compile`` children, and the paged prefix-cached scheduler records the
admission phases and each step's grow / interval / harvest with the counts
the benchmark reads, without changing a single served token.
"""
import threading
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import tracing
from repro.core.runtime import Runtime
from repro.core.tracing import NOOP, Tracer
from repro.models import build
from repro.serve.scheduler import ContinuousBatchingScheduler, Request

K = 3  # sync interval of the paged schedulers below


@pytest.fixture(scope="module")
def bundle():
    cfg = get_config("gemma3-1b", reduced=True)
    model = build(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _tree(spans):
    by_id = {s.id: s for s in spans}
    kids = defaultdict(list)
    for s in spans:
        kids[s.parent_id].append(s)
    for v in kids.values():
        v.sort(key=lambda s: (s.t0_ns, s.id))
    return by_id, kids


def _named(spans, name):
    return [s for s in spans if s.name == name]


# -- the tracer alone ----------------------------------------------------------


def test_disabled_tracer_records_nothing_and_hands_out_the_shared_noop():
    tr = Tracer()
    assert tr.enabled is False
    a, b = tr.span("a"), tr.span("b", rid="x")
    assert a is NOOP and b is NOOP
    with a as sp:
        sp.set(tokens=3)
    assert tr.drain() == ([], 0)


def test_disabled_span_reads_no_clock_and_makes_no_object(monkeypatch):
    def no_clock():
        raise AssertionError("the clock was read with tracing off")

    monkeypatch.setattr(tracing, "perf_counter_ns", no_clock)
    tr = Tracer()
    handed = set()
    for i in range(1000):
        with tr.span("serve.step", i=i) as sp:
            sp.set(tokens=i)
            handed.add(id(sp))
    assert handed == {id(NOOP)} and tr.drain() == ([], 0)


def test_runtime_owns_a_disabled_tracer_and_run_skips_it(monkeypatch):
    with Runtime("jaxdev") as rt:
        assert isinstance(rt.tracer, Tracer) and not rt.tracer.enabled
        unit = rt.create_execution_unit(lambda x: x + 1, name="inc")
        monkeypatch.setattr(tracing, "perf_counter_ns",
                            lambda: (_ for _ in ()).throw(AssertionError("clock read")))
        assert int(rt.run(unit, jnp.int32(1))) == 2
        assert rt.tracer.drain() == ([], 0)


def test_run_records_the_wait_inside_the_run():
    with Runtime("jaxdev") as rt:
        unit = rt.create_execution_unit(lambda x: x * 3, name="triple")
        rt.run(unit, jnp.ones((4,)))  # compile before tracing
        rt.tracer.enable()
        rt.run(unit, jnp.ones((4,)))
        spans, dropped = rt.tracer.drain()
    assert dropped == 0
    [run] = _named(spans, "hicr.run:triple")
    [wait] = _named(spans, "hicr.wait:triple")
    assert run.parent_id is None and wait.parent_id == run.id
    assert run.t0_ns <= wait.t0_ns <= wait.t1_ns <= run.t1_ns


def test_nesting_and_parents_stay_per_thread():
    tr = Tracer()
    tr.enable()
    barrier = threading.Barrier(2, timeout=10)

    def work(tag):
        with tr.span("outer", tag=tag):
            barrier.wait()  # both outers open before either inner
            with tr.span("inner", tag=tag) as sp:
                barrier.wait()
                sp.set(done=True)

    threads = [threading.Thread(target=work, args=(t,)) for t in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    spans, _ = tr.drain()
    by_id, _ = _tree(spans)
    inners = _named(spans, "inner")
    assert len(inners) == 2 and len(_named(spans, "outer")) == 2
    for s in inners:
        parent = by_id[s.parent_id]
        assert parent.name == "outer" and parent.attrs["tag"] == s.attrs["tag"]
        assert s.attrs["done"] is True
    assert all(s.parent_id is None for s in _named(spans, "outer"))


def test_a_full_ring_drops_the_oldest_and_counts_them():
    tr = Tracer()
    tr.enable(capacity=3)
    for i in range(5):
        with tr.span("s", i=i):
            pass
    spans, dropped = tr.drain()
    assert dropped == 2 and [s.attrs["i"] for s in spans] == [2, 3, 4]
    assert tr.drain() == ([], 0)
    with pytest.raises(ValueError):
        tr.enable(capacity=0)


def test_disable_stops_recording():
    tr = Tracer()
    tr.enable()
    with tr.span("kept"):
        pass
    tr.disable()
    assert tr.span("dropped") is NOOP
    assert [s.name for s in tr.drain().spans] == ["kept"]


def test_a_compile_inside_a_span_is_its_child():
    tr = Tracer()
    tr.enable()
    with tr.span("outer") as outer:  # a new function: jit compiles it here
        jax.jit(lambda x: x * 2 + 1.5)(jnp.ones((3,))).block_until_ready()
    jax.jit(lambda x: x - 1.5)(jnp.ones((5,))).block_until_ready()  # no span open
    spans, _ = tr.drain()
    [rec] = _named(spans, "outer")
    compiles = _named(spans, "xla.compile")
    assert compiles and all(c.parent_id == rec.id for c in compiles)
    assert all(rec.t0_ns <= c.t0_ns <= c.t1_ns <= rec.t1_ns for c in compiles)
    assert outer is not NOOP


# -- the serve path's spans -----------------------------------------------------


def _requests(cfg, n, seed):
    rng = np.random.default_rng(seed)
    system = rng.integers(1, cfg.vocab_size, (18,)).tolist()
    reqs = []
    for i in range(n):
        tail = rng.integers(1, cfg.vocab_size, (int(rng.integers(2, 9)),)).tolist()
        prompt = (system + tail) if i % 2 else tail
        reqs.append(Request(rid=f"r{i}", prompt=prompt,
                            max_new_tokens=1 if i == 3 else int(rng.integers(2, 11))))
    return reqs


def _paged(bundle, **kw):
    _, model, params = bundle
    args = dict(max_batch=3, max_len=64, kv_mode="paged", page_size=16,
                sync_interval=K, prefix_cache=True)
    args.update(kw)
    return ContinuousBatchingScheduler(model, params, **args)


def _serve_in_order(sched, reqs):
    """Serve the requests in two waves, so later ones can hit the cache."""
    results = {}
    for wave in (reqs[: len(reqs) // 2], reqs[len(reqs) // 2:]):
        results.update(sched.serve(wave))
    return results


def test_paged_prefix_spans_form_the_documented_tree(bundle):
    cfg = bundle[0]
    sched = _paged(bundle)
    tr = sched.decoder.rt.tracer
    reqs = _requests(cfg, 8, seed=3)
    tr.enable()
    results = _serve_in_order(sched, reqs)
    spans, dropped = tr.drain()
    assert dropped == 0
    by_id, kids = _tree(spans)

    admits = _named(spans, "serve.admit")
    outcomes = defaultdict(int)
    for a in admits:
        outcomes[a.attrs["outcome"]] += 1
        assert a.attrs["rid"] and a.attrs["prompt_len"] > 0
        if a.attrs["outcome"] != "seated":
            continue
        phases = [k.name for k in kids[a.id]]
        assert phases == ["serve.admit.match", "serve.admit.reserve",
                          "serve.admit.prefill", "serve.admit.commit"]
        reserve, prefill = kids[a.id][1], kids[a.id][2]
        assert reserve.attrs["evicted"] == 0
        assert prefill.attrs["tail_len"] == a.attrs["prompt_len"] - a.attrs["matched_len"]
        assert [k.name for k in kids[prefill.id]] == ["hicr.run:prefix_prefill"]
        commit = kids[a.id][3]
        assert [k.name for k in kids[commit.id]] == ["hicr.run:commit_and_arm"]
    assert outcomes["seated"] == 7 and outcomes["finished"] == 1
    assert outcomes["no_slot"] >= 1  # serve() offers the next request to a full table
    assert any(a.attrs["matched_len"] > 0 for a in admits if "matched_len" in a.attrs)

    steps = _named(spans, "serve.step")
    assert steps
    for s in steps:
        grow, interval, harvest = kids[s.id]
        assert (grow.name, interval.name, harvest.name) == (
            "serve.step.grow", "serve.step.interval", "serve.step.harvest")
        assert grow.attrs["drawn"] >= 0
        [run] = kids[interval.id]
        assert run.name == f"hicr.run:fused_decode_x{K}"
        # the first interval compiles its unit: a child beside the wait
        [wait] = [k for k in kids[run.id] if k.name != "xla.compile"]
        assert wait.name == f"hicr.wait:fused_decode_x{K}"
        assert s.attrs["slots"] == 3 * K and 0 < s.attrs["tokens"] <= s.attrs["slots"]
    assert by_id[_named(spans, "xla.compile")[0].parent_id].name.startswith("hicr.run:")
    decoded = sum(len(f.tokens) - 1 for f in results.values())
    assert sum(s.attrs["tokens"] for s in steps) == decoded
    assert sum(s.attrs["finished"] for s in steps) == 7
    # every span closed inside its parent
    for s in spans:
        if s.parent_id is not None:
            p = by_id[s.parent_id]
            assert p.t0_ns <= s.t0_ns <= s.t1_ns <= p.t1_ns


def test_a_refusal_for_pages_is_recorded_with_its_outcome(bundle):
    sched = _paged(bundle, max_batch=4, max_len=48, pool_pages=4, prefix_cache=False)
    tr = sched.decoder.rt.tracer
    tr.enable()
    assert sched.try_admit(Request(rid="a", prompt=[1] * 10, max_new_tokens=20))
    assert not sched.try_admit(Request(rid="b", prompt=[2] * 10, max_new_tokens=20))
    spans, _ = tr.drain()
    _, kids = _tree(spans)
    a, b = sorted(_named(spans, "serve.admit"), key=lambda s: s.t0_ns)
    assert (a.attrs["outcome"], b.attrs["outcome"]) == ("seated", "no_pages")
    assert [k.name for k in kids[b.id]] == ["serve.admit.match", "serve.admit.reserve"]
    assert "matched_len" not in a.attrs  # no prefix cache, no match


def test_dense_steps_count_slots_and_tokens(bundle):
    cfg, model, params = bundle
    sched = ContinuousBatchingScheduler(model, params, max_batch=2, max_len=48)
    tr = sched.decoder.rt.tracer
    reqs = _requests(cfg, 4, seed=5)
    tr.enable()
    results = sched.serve(reqs)
    spans, _ = tr.drain()
    _, kids = _tree(spans)
    steps = _named(spans, "serve.step")
    assert all(s.attrs["slots"] == 2 for s in steps)
    assert sum(s.attrs["tokens"] for s in steps) == sum(
        len(f.tokens) - 1 for f in results.values())
    for a in _named(spans, "serve.admit"):
        if a.attrs["outcome"] == "seated":
            assert [k.name for k in kids[a.id]] == ["serve.admit.prefill",
                                                    "serve.admit.commit"]


@pytest.mark.parametrize("kv_mode", ["paged", "dense"])
def test_tokens_are_identical_with_the_tracer_on_and_off(bundle, kv_mode):
    cfg, model, params = bundle
    reqs = _requests(cfg, 6, seed=9)
    out = []
    for on in (False, True):
        if kv_mode == "paged":
            sched = _paged(bundle)
        else:
            sched = ContinuousBatchingScheduler(model, params, max_batch=3, max_len=64)
        if on:
            sched.decoder.rt.tracer.enable()
        results = _serve_in_order(sched, reqs)
        out.append({rid: (f.tokens, f.finish_reason) for rid, f in results.items()})
        assert bool(sched.decoder.rt.tracer.drain().spans) is on
    assert out[0] == out[1]


def test_interval_counts_live_and_table_kv_pages_only_when_tracing(bundle, monkeypatch):
    """On, every `serve.step.interval` carries the pages the decode kernel
    walks (`kv_pages_live`) against the active slots' table pages
    (`kv_pages_table`); off, the count is never taken."""
    cfg = bundle[0]
    sched = _paged(bundle)
    tr = sched.decoder.rt.tracer
    reqs = _requests(cfg, 6, seed=11)
    tr.enable()
    _serve_in_order(sched, reqs)
    spans, _ = tr.drain()
    n_table = sched.decoder.layout.n_pages_seq
    intervals = _named(spans, "serve.step.interval")
    assert intervals
    for s in intervals:
        live, table = s.attrs["kv_pages_live"], s.attrs["kv_pages_table"]
        assert 0 < live <= table and table % n_table == 0
    assert any(s.attrs["kv_pages_live"] < s.attrs["kv_pages_table"] for s in intervals)

    off = _paged(bundle)

    def never(self):
        raise AssertionError("page counts taken with the tracer off")

    monkeypatch.setattr(ContinuousBatchingScheduler, "_kv_pages", never)
    assert len(_serve_in_order(off, reqs)) == len(reqs)
    assert off.decoder.rt.tracer.drain().spans == []
