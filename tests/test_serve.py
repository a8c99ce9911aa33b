"""Serving stack: serial engine determinism, continuous-batching scheduler
(slot table, mid-decode admission, eviction), and the Channels-driven
request front door over localsim."""
import json
import logging

import jax
import numpy as np
import pytest

from repro.configs import ShapeConfig, get_config
from repro.core.runtime import Runtime
from repro.frontends.channels import ChannelMessageTooLargeError
from repro.models import build
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import ContinuousBatchingScheduler, FinishedRequest, Request
from repro.serve.server import ChannelServer


@pytest.fixture(scope="module")
def bundle():
    cfg = get_config("gemma3-1b", reduced=True)
    model = build(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def engine(bundle):
    _, model, params = bundle
    return ServeEngine(model, params, max_len=64)


class TestServeEngine:
    def test_generates_requested_steps(self, engine):
        prompts = np.array([[1, 2, 3, 4, 5, 6, 7, 8]], dtype=np.int32)
        result = engine.generate(prompts, steps=6)
        assert result.tokens.shape == (1, 6)
        assert result.prefill_logits.shape[0] == 1

    def test_generation_is_deterministic(self, engine):
        prompts = np.array([[9, 8, 7, 6, 5, 4, 3, 2]], dtype=np.int32)
        a = engine.generate(prompts, steps=5)
        b = engine.generate(prompts, steps=5)
        np.testing.assert_array_equal(a.tokens, b.tokens)

    def test_batched_generation_matches_single(self, engine):
        """Row i of a batched generate equals generating row i alone —
        no cross-request leakage through the KV cache."""
        p1 = np.array([[1, 2, 3, 4, 5, 6, 7, 8]], dtype=np.int32)
        p2 = np.array([[11, 12, 13, 14, 15, 16, 17, 18]], dtype=np.int32)
        both = engine.generate(np.concatenate([p1, p2]), steps=4)
        solo1 = engine.generate(p1, steps=4)
        solo2 = engine.generate(p2, steps=4)
        np.testing.assert_array_equal(both.tokens[0], solo1.tokens[0])
        np.testing.assert_array_equal(both.tokens[1], solo2.tokens[0])

    def test_decode_beyond_prompt_length_no_clamp(self):
        """Regression: decode steps past the prompt length must keep writing
        new cache entries (prefill allocates max_len headroom), so late
        tokens still depend on mid-generation tokens."""
        cfg = get_config("granite-20b", reduced=True)
        model = build(cfg)
        params, _ = model.init(jax.random.PRNGKey(1))
        eng = ServeEngine(model, params, max_len=40)
        prompts = np.array([[5, 6, 7, 8]], dtype=np.int32)
        result = eng.generate(prompts, steps=20)  # 4 + 20 < 40: all in cache
        assert result.tokens.shape == (1, 20)

    def test_engine_runs_on_hostcpu_runtime(self, bundle):
        """Backend swap through the Runtime facade: same engine code, hostcpu
        compute manager (unjitted python-callable path)."""
        _, model, params = bundle
        eng = ServeEngine(model, params, max_len=16, runtime=Runtime("hostcpu"))
        prompts = np.array([[1, 2, 3]], dtype=np.int32)
        assert eng.generate(prompts, steps=2).tokens.shape == (1, 2)


def _workload(cfg, n, *, seed=0, lo_p=3, hi_p=12, lo_s=2, hi_s=14):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(lo_p, hi_p))
        steps = int(rng.integers(lo_s, hi_s))
        prompt = rng.integers(1, cfg.vocab_size, (plen,)).tolist()
        reqs.append(Request(rid=f"r{seed}-{i}", prompt=prompt, max_new_tokens=steps))
    return reqs


class TestContinuousBatchingScheduler:
    def test_matches_serial_engine_tokens(self, bundle, engine):
        """Continuous batching is a scheduling change, not a model change:
        every request's tokens equal the serial engine's output."""
        cfg, model, params = bundle
        sched = ContinuousBatchingScheduler(model, params, max_batch=4, max_len=64)
        reqs = _workload(cfg, 6)
        results = sched.serve(reqs)
        for r in reqs:
            serial = engine.generate(
                np.asarray([r.prompt], dtype=np.int32), steps=r.max_new_tokens
            ).tokens[0].tolist()
            assert results[r.rid].tokens == serial, r.rid

    def test_eight_concurrent_requests_varied_lengths(self, bundle):
        """Acceptance shape: >= 8 requests of different prompt/decode lengths
        in flight concurrently on an 8-slot table."""
        cfg, model, params = bundle
        sched = ContinuousBatchingScheduler(model, params, max_batch=8, max_len=64)
        reqs = _workload(cfg, 8, lo_s=6, hi_s=14)
        assert len({len(r.prompt) for r in reqs}) > 1
        for r in reqs:
            assert sched.try_admit(r)
        assert sched.active_count == 8 and sched.free_slots == 0
        results = {}
        while len(results) < 8:
            for fin in sched.step():
                results[fin.rid] = fin
        for r in reqs:
            assert len(results[r.rid].tokens) == r.max_new_tokens
            assert results[r.rid].finish_reason == "length"

    def test_admission_mid_decode(self, bundle, engine):
        """A request admitted while others are mid-decode joins the running
        batch without perturbing their outputs."""
        cfg, model, params = bundle
        sched = ContinuousBatchingScheduler(model, params, max_batch=4, max_len=64)
        early = _workload(cfg, 2, seed=1, lo_s=8, hi_s=9)
        late = _workload(cfg, 1, seed=2, lo_s=4, hi_s=5)[0]
        for r in early:
            assert sched.try_admit(r)
        results = {}
        for fin in sched.step():  # early requests are now mid-decode
            results[fin.rid] = fin
        assert sched.try_admit(late)
        assert sched.active_count == 3
        while len(results) < 3:
            for fin in sched.step():
                results[fin.rid] = fin
        for r in early + [late]:
            serial = engine.generate(
                np.asarray([r.prompt], dtype=np.int32), steps=r.max_new_tokens
            ).tokens[0].tolist()
            assert results[r.rid].tokens == serial

    def test_slots_are_recycled(self, bundle):
        """Eviction frees the slot for the next admission: more requests than
        slots complete on a small table."""
        cfg, model, params = bundle
        sched = ContinuousBatchingScheduler(model, params, max_batch=2, max_len=64)
        reqs = _workload(cfg, 7, seed=3)
        results = sched.serve(reqs)
        assert set(results) == {r.rid for r in reqs}
        assert sched.active_count == 0 and sched.free_slots == 2

    def test_admission_denied_when_full_then_allowed(self, bundle):
        cfg, model, params = bundle
        sched = ContinuousBatchingScheduler(model, params, max_batch=2, max_len=64)
        reqs = _workload(cfg, 3, seed=4, lo_s=3, hi_s=4)
        assert sched.try_admit(reqs[0])
        assert sched.try_admit(reqs[1])
        assert not sched.try_admit(reqs[2])  # table full
        done = []
        while not done:
            done = sched.step()
        assert sched.try_admit(reqs[2])  # freed slot is reusable

    def test_eos_evicts_early(self, bundle, engine):
        """A request whose greedy chain hits eos_id finishes with reason
        'eos' and a shortened token list."""
        cfg, model, params = bundle
        prompt = [7, 3, 9, 1]
        serial = engine.generate(np.asarray([prompt], dtype=np.int32), steps=8)
        chain = serial.tokens[0].tolist()
        eos = chain[3]  # the greedy chain may repeat: stop at FIRST occurrence
        stop = chain.index(eos)
        sched = ContinuousBatchingScheduler(model, params, max_batch=2, max_len=64)
        results = sched.serve(
            [Request(rid="e", prompt=prompt, max_new_tokens=8, eos_id=eos)]
        )
        assert results["e"].finish_reason == "eos"
        assert results["e"].tokens == chain[: stop + 1]

    def test_single_token_request_bypasses_slots(self, bundle):
        cfg, model, params = bundle
        sched = ContinuousBatchingScheduler(model, params, max_batch=2, max_len=64)
        assert sched.try_admit(Request(rid="one", prompt=[1, 2, 3], max_new_tokens=1))
        assert sched.active_count == 0  # finished at prefill, no slot taken
        [fin] = sched.step()
        assert fin.rid == "one" and len(fin.tokens) == 1

    def test_oversized_request_rejected(self, bundle):
        cfg, model, params = bundle
        sched = ContinuousBatchingScheduler(model, params, max_batch=2, max_len=16)
        with pytest.raises(ValueError, match="cache positions"):
            sched.try_admit(Request(rid="big", prompt=[1] * 10, max_new_tokens=10))


class TestPagedScheduler:
    """kv_mode='paged': block-pool KV cache + device-resident decode loop.
    Paging and interval fusion are scheduling/storage changes only — outputs
    must stay token-identical to the dense path (and hence to the serial
    engine, which the dense path is tested against above)."""

    def test_paged_matches_serial_engine_tokens(self, bundle, engine):
        """Extends the scheduler-vs-serial identity test: the paged decoder
        with sync_interval>1 (mid-interval finishes freeze in place) still
        reproduces the serial engine's tokens exactly."""
        cfg, model, params = bundle
        sched = ContinuousBatchingScheduler(
            model, params, max_batch=4, max_len=64,
            kv_mode="paged", page_size=16, sync_interval=4,
        )
        reqs = _workload(cfg, 6)
        results = sched.serve(reqs)
        for r in reqs:
            serial = engine.generate(
                np.asarray([r.prompt], dtype=np.int32), steps=r.max_new_tokens
            ).tokens[0].tolist()
            assert results[r.rid].tokens == serial, r.rid
        assert sched.decoder.kv.pages_used == 0  # every eviction freed its pages

    def test_paged_matches_dense_with_eos_mid_interval(self, bundle, engine):
        """An eos hit inside a fused interval must cut the emission at the
        same token as the per-tick dense path."""
        cfg, model, params = bundle
        prompt = [7, 3, 9, 1]
        chain = engine.generate(np.asarray([prompt], dtype=np.int32), steps=8).tokens[0].tolist()
        eos = chain[3]
        stop = chain.index(eos)
        sched = ContinuousBatchingScheduler(
            model, params, max_batch=2, max_len=64,
            kv_mode="paged", sync_interval=5,
        )
        results = sched.serve([Request(rid="e", prompt=prompt, max_new_tokens=8, eos_id=eos)])
        assert results["e"].finish_reason == "eos"
        assert results["e"].tokens == chain[: stop + 1]

    def test_window_model_with_a_page_not_dividing_its_window(self, bundle, engine):
        """gemma3's window layers (window 16) page through the same table as
        its global layers, so a page of 12 positions serves them with the
        cache off; prompts and outputs run past the window and across page
        boundaries, and the tokens equal the serial engine's ring buffers."""
        cfg, model, params = bundle
        sched = ContinuousBatchingScheduler(
            model, params, max_batch=3, max_len=64,
            kv_mode="paged", page_size=12, sync_interval=4,
        )
        reqs = _workload(cfg, 5, seed=3, lo_p=8, hi_p=20, lo_s=6, hi_s=16)
        assert max(len(r.prompt) + r.max_new_tokens for r in reqs) > cfg.sliding_window + 12
        results = sched.serve(reqs)
        for r in reqs:
            serial = engine.generate(
                np.asarray([r.prompt], dtype=np.int32), steps=r.max_new_tokens
            ).tokens[0].tolist()
            assert results[r.rid].tokens == serial, r.rid
        assert sched.decoder.kv.pages_used == 0

    def test_page_availability_admission_control(self, bundle):
        """Admission is bounded by free pool pages, not just free slots: a
        pool sized for one request backpressures the second until eviction
        frees its pages, and a request larger than the whole pool is
        rejected as unservable."""
        cfg, model, params = bundle
        sched = ContinuousBatchingScheduler(
            model, params, max_batch=4, max_len=48,
            kv_mode="paged", page_size=16, pool_pages=4, sync_interval=4,
        )
        a = Request(rid="a", prompt=[1] * 10, max_new_tokens=20)
        b = Request(rid="b", prompt=[2] * 10, max_new_tokens=20)
        assert sched.try_admit(a)
        assert sched.free_slots > 0 and not sched.try_admit(b)  # page pressure
        results = {}
        while "a" not in results:
            for fin in sched.step():
                results[fin.rid] = fin
        assert sched.try_admit(b)  # freed pages readmit
        # a request needing more pages than the whole pool holds can never
        # be admitted: permanently unservable, not backpressure
        tiny = ContinuousBatchingScheduler(
            model, params, max_batch=2, max_len=48,
            kv_mode="paged", page_size=16, pool_pages=3, sync_interval=4,
        )
        with pytest.raises(ValueError, match="KV pages"):
            tiny.try_admit(Request(rid="big", prompt=[3] * 30, max_new_tokens=17))

    def test_active_progress_surfaces_pool_occupancy(self, bundle):
        cfg, model, params = bundle
        sched = ContinuousBatchingScheduler(
            model, params, max_batch=2, max_len=32,
            kv_mode="paged", page_size=16, sync_interval=2,
        )
        assert sched.try_admit(Request(rid="p", prompt=[1, 2, 3], max_new_tokens=6))
        prog = sched.active_progress()
        assert set(prog.requests) == {"p"} and len(prog.requests["p"]) == 1
        assert prog.pages_used >= 1
        assert prog.pages_free == sched.decoder.kv.capacity - prog.pages_used
        # dense mode has no shared pool to meter
        dense = ContinuousBatchingScheduler(model, params, max_batch=2, max_len=32)
        dprog = dense.active_progress()
        assert dprog.pages_free is None and dprog.pages_used is None

    def test_paged_channel_server_matches_terse_protocol(self, bundle):
        """The channel front door over a paged scheduler settles the same
        token lists as the dense one (transport + storage orthogonality)."""
        from collections import deque

        class FakeConsumer:
            def __init__(self, msgs):
                self.msgs = deque(msgs)

            def try_pop(self):
                return self.msgs.popleft() if self.msgs else None

        class FakeReply:
            def __init__(self):
                self.out = []

            def push(self, data):
                self.out.append(json.loads(bytes(data).rstrip(b"\0").decode()))

        _, model, params = bundle
        reqs = [
            {"id": "a", "prompt": [1, 2, 3], "steps": 9},
            {"id": "b", "prompt": [4, 5, 6, 7], "steps": 6},
        ]
        msgs = [json.dumps(r).encode().ljust(256, b"\0") for r in reqs]
        dense = ContinuousBatchingScheduler(model, params, max_batch=2, max_len=32)
        terse = FakeReply()
        ChannelServer(dense, FakeConsumer(list(msgs)), terse, msg_size=256).serve(2)
        paged = ContinuousBatchingScheduler(
            model, params, max_batch=2, max_len=32, kv_mode="paged", sync_interval=3
        )
        pr = FakeReply()
        ChannelServer(paged, FakeConsumer(list(msgs)), pr, msg_size=256).serve(2)
        assert {r["id"]: r["tokens"] for r in pr.out} == \
            {r["id"]: r["tokens"] for r in terse.out}

    def test_unknown_kv_mode_rejected(self, bundle):
        _, model, params = bundle
        with pytest.raises(ValueError, match="kv_mode"):
            ContinuousBatchingScheduler(model, params, kv_mode="sparse")

    def test_paged_requires_family_support(self, bundle):
        """Families without a pure-KV decode state get a clear error."""
        cfg = get_config("xlstm-125m", reduced=True)
        model = build(cfg)
        params, _ = model.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="no paged KV-cache path"):
            ContinuousBatchingScheduler(model, params, max_batch=2, max_len=32,
                                        kv_mode="paged")


def _in_place_arch(name):
    """granite-20b: one layer stack; gemma3-tail: a sliding-window unit and a
    two-layer tail, the tail's pools carried like the stack's."""
    if name == "granite-20b":
        return get_config("granite-20b", reduced=True)
    return get_config("gemma3-1b", reduced=True).replace(num_layers=8)


class TestPagedPoolInPlace:
    """The paged decoder writes its pool in place: `commit_and_arm` takes
    it donated, and the fused interval writes only the new rows of the
    pool it returns, leaving the pool it was given intact."""

    @pytest.mark.parametrize("arch, prefix_cache", [
        ("granite-20b", True), ("gemma3-tail", True), ("gemma3-tail", False)])
    def test_donated_pool_through_admissions_intervals_evictions(self, arch, prefix_cache):
        """Requests sharing a prefix, more of them than slots, through a pool
        small enough to evict: tail prefills read the pool (with the cache
        off, every one a miss against null pages), commits and intervals
        write it, and every output equals the dense path's. Each commit
        consumes the pool it was given; an interval leaves its pool
        readable, and its unit reports its memory."""
        cfg = _in_place_arch(arch)
        model = build(cfg)
        params, _ = model.init(jax.random.PRNGKey(2))
        rng = np.random.default_rng(5)
        system = rng.integers(1, cfg.vocab_size, (10,)).tolist()
        reqs = [
            Request(rid=f"d{i}", max_new_tokens=int(rng.integers(3, 12)),
                    prompt=(system if i % 2 else []) + rng.integers(
                        1, cfg.vocab_size, (int(rng.integers(3, 9)),)).tolist())
            for i in range(8)
        ]
        dense = ContinuousBatchingScheduler(model, params, max_batch=3, max_len=32).serve(reqs)
        sched = ContinuousBatchingScheduler(
            model, params, max_batch=3, max_len=32, kv_mode="paged", page_size=4,
            pool_pages=20, sync_interval=3, prefix_cache=prefix_cache,
        )
        dec = sched.decoder
        pages = {x.shape[-4] for x in jax.tree_util.tree_leaves(dec.kv.pools)}
        assert pages == {dec.layout.num_pages}  # window layers included
        leaves = lambda: jax.tree_util.tree_leaves(dec.kv.pools)  # noqa: E731
        backlog, results, committed, kept = list(reqs), {}, [], []
        while len(results) < len(reqs):
            while backlog:
                given = leaves()
                if not sched.try_admit(backlog[0]):
                    break
                backlog.pop(0)
                committed.append(all(x.is_deleted() for x in given))
            given = leaves()
            for fin in sched.step():
                results[fin.rid] = fin
            kept.append(not any(x.is_deleted() for x in given))
        for r in reqs:
            assert results[r.rid].tokens == dense[r.rid].tokens, r.rid
        assert len(committed) == len(reqs) and all(committed)
        assert all(kept)
        if prefix_cache:
            stats = sched.prefix.stats()
            assert stats["hits"] >= 1 and stats["evictions"] >= 1
        assert set(dec.stats()) == {"pool_alias_bytes", "unit_temp_bytes"}
        assert dec._commit_unit.metadata["donate_argnums"] == (0, 3)
        assert dec._fused_unit.metadata["donate_argnums"] == ()

    def test_stats_read_the_unit_an_interval_compiled(self, caplog):
        """The scheduler reads `stats()` while tracing, after an interval:
        the executable it reads is the one the interval compiled, so the
        read compiles nothing."""
        cfg = get_config("granite-20b", reduced=True)
        model = build(cfg)
        params, _ = model.init(jax.random.PRNGKey(2))
        sched = ContinuousBatchingScheduler(
            model, params, max_batch=2, max_len=16, kv_mode="paged", sync_interval=2)
        sched.serve([Request(rid="s", prompt=[5, 6, 7], max_new_tokens=4)])
        with caplog.at_level(logging.WARNING, logger="jax"), jax.log_compiles(True):
            stats = sched.decoder.stats()
        assert stats["unit_temp_bytes"] > 0
        assert not [r for r in caplog.messages if "XLA compilation" in r]

    def test_unjitted_backend_reports_no_unit_memory(self):
        """On hostcpu the units run unstaged: nothing is donated, and the
        decoder reports no executable's memory."""
        cfg = get_config("granite-20b", reduced=True)
        model = build(cfg)
        params, _ = model.init(jax.random.PRNGKey(2))
        sched = ContinuousBatchingScheduler(
            model, params, max_batch=1, max_len=16, kv_mode="paged", sync_interval=2,
            runtime=Runtime("hostcpu"),
        )
        [fin] = sched.serve([Request(rid="h", prompt=[5, 6, 7], max_new_tokens=3)]).values()
        assert len(fin.tokens) == 3
        assert sched.decoder.stats() == {}


class TestPrefixCacheScheduler:
    """prefix_cache=True over the paged scheduler: prefix sharing is a
    storage/scheduling change only — outputs stay token-identical to the
    serial engine while shared prompts skip their prefix's prefill."""

    def test_shared_prompt_workload_matches_serial(self, bundle, engine):
        """Requests sharing a system prompt (and sequential resubmissions
        that fully hit) reproduce the serial engine's tokens exactly, and
        eviction accounting balances: after the drain the pool holds
        exactly the cache's pages."""
        cfg, model, params = bundle
        rng = np.random.default_rng(7)
        system = rng.integers(1, cfg.vocab_size, (20,)).tolist()
        reqs = []
        for i in range(6):
            if i % 2 == 0:
                prompt = system + rng.integers(1, cfg.vocab_size, (1 + i,)).tolist()
            else:
                prompt = rng.integers(1, cfg.vocab_size, (8,)).tolist()
            reqs.append(Request(rid=f"p{i}", prompt=prompt, max_new_tokens=4 + i))
        sched = ContinuousBatchingScheduler(
            model, params, max_batch=2, max_len=64,
            kv_mode="paged", page_size=16, sync_interval=3, prefix_cache=True,
        )
        # serve sequentially so later shared requests actually hit the cache
        results = {}
        for r in reqs:
            results.update(sched.serve([r]))
        for r in reqs:
            serial = engine.generate(
                np.asarray([r.prompt], dtype=np.int32), steps=r.max_new_tokens
            ).tokens[0].tolist()
            assert results[r.rid].tokens == serial, r.rid
        stats = sched.prefix.stats()
        assert stats["hits"] >= 2 and stats["hit_tokens"] >= 16
        assert sched.decoder.kv.pages_used == sched.prefix.cached_pages

    def test_identical_resubmission_is_a_full_hit(self, bundle, engine):
        """The same request twice: the second admission matches everything
        but the clamped final token and still emits identical output."""
        cfg, model, params = bundle
        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2, 3]
        sched = ContinuousBatchingScheduler(
            model, params, max_batch=2, max_len=48,
            kv_mode="paged", page_size=16, sync_interval=2, prefix_cache=True,
        )
        first = sched.serve([Request(rid="a", prompt=prompt, max_new_tokens=6)])
        again = sched.serve([Request(rid="b", prompt=prompt, max_new_tokens=6)])
        assert again["b"].tokens == first["a"].tokens
        serial = engine.generate(
            np.asarray([prompt], dtype=np.int32), steps=6
        ).tokens[0].tolist()
        assert first["a"].tokens == serial
        stats = sched.prefix.stats()
        assert stats["hits"] == 1
        # the first serve wrote 23 positions -> exactly one full page was
        # donated; the resubmission shares those 16 tokens by reference
        assert stats["hit_tokens"] == 16

    def test_multi_turn_resumption_matches_serial(self, bundle, engine):
        """Turn 2 resumes turn 1's history (prompt + full reply + followup):
        nearly all of it forks from the cache, output stays serial-exact."""
        from repro.serve.workload import multi_turn_requests, resume_prompt

        cfg, model, params = bundle
        sched = ContinuousBatchingScheduler(
            model, params, max_batch=2, max_len=64,
            kv_mode="paged", page_size=16, sync_interval=2, prefix_cache=True,
        )
        # steps pinned to 9 so turn 1 writes 8 + 9 - 1 = 16 positions —
        # exactly one full page for turn 2 to fork
        [[turn1, turn2]] = multi_turn_requests(
            cfg.vocab_size, 1, 2, first_prompt_range=(8, 9),
            followup_range=(3, 4), steps_range=(9, 10), seed=4,
        )
        r1 = sched.serve([turn1])[turn1.rid]
        prompt2 = resume_prompt(turn1.prompt, r1.tokens, turn2.prompt)
        r2 = sched.serve(
            [Request(rid=turn2.rid, prompt=prompt2,
                     max_new_tokens=turn2.max_new_tokens)]
        )[turn2.rid]
        serial = engine.generate(
            np.asarray([prompt2], dtype=np.int32), steps=turn2.max_new_tokens
        ).tokens[0].tolist()
        assert r2.tokens == serial
        assert sched.prefix.stats()["hit_tokens"] >= 16

    def test_eviction_under_page_pressure(self, bundle, engine):
        """A pool too small to retain every finished request's pages evicts
        LRU cache entries instead of refusing admission; outputs stay exact
        and no page is ever leaked or double-freed (LifetimeError would
        surface here)."""
        cfg, model, params = bundle
        sched = ContinuousBatchingScheduler(
            model, params, max_batch=2, max_len=48,
            kv_mode="paged", page_size=16, pool_pages=6, sync_interval=2,
            prefix_cache=True,
        )
        rng = np.random.default_rng(11)
        for i in range(5):
            prompt = rng.integers(1, cfg.vocab_size, (18,)).tolist()
            [fin] = sched.serve(
                [Request(rid=f"e{i}", prompt=prompt, max_new_tokens=5)]
            ).values()
            serial = engine.generate(
                np.asarray([prompt], dtype=np.int32), steps=5
            ).tokens[0].tolist()
            assert fin.tokens == serial, f"e{i}"
        assert sched.prefix.stats()["evictions"] >= 1
        assert sched.decoder.kv.pages_used == sched.prefix.cached_pages

    def test_own_locked_match_cannot_livelock_admission(self, bundle, engine):
        """Regression: when the ONLY evictable pages are the ones the
        request's own match just locked (and nothing is in flight to free
        pages later), admission must demote the match to a miss and evict —
        not return False forever and livelock serve()."""
        cfg, model, params = bundle
        sched = ContinuousBatchingScheduler(
            model, params, max_batch=2, max_len=12,
            kv_mode="paged", page_size=4, pool_pages=4, sync_interval=2,
            prefix_cache=True,
        )
        a_prompt = [1, 2, 3, 4, 5, 6, 7, 8]
        sched.serve([Request(rid="a", prompt=a_prompt, max_new_tokens=2)])
        assert sched.prefix.cached_pages == 2  # the whole pool's capacity - 1
        # B shares page 1 and reaches 2 tokens into page 2 (boundary): its
        # lock pins BOTH cached pages; it needs 2 new pages but only 1 is
        # free — the demote-to-miss path must reclaim the cache and admit
        b_prompt = a_prompt[:6] + [91, 92]
        b = Request(rid="b", prompt=b_prompt, max_new_tokens=2)
        assert sched.try_admit(b) is True
        results = {}
        while "b" not in results:
            for fin in sched.step():
                results[fin.rid] = fin
        serial = engine.generate(
            np.asarray([b_prompt], dtype=np.int32), steps=2
        ).tokens[0].tolist()
        assert results["b"].tokens == serial

    def test_progress_surfaces_prefix_stats(self, bundle):
        cfg, model, params = bundle
        sched = ContinuousBatchingScheduler(
            model, params, max_batch=2, max_len=32,
            kv_mode="paged", page_size=16, sync_interval=2, prefix_cache=True,
        )
        sched.serve([Request(rid="s", prompt=[1, 2, 3, 4], max_new_tokens=3)])
        prog = sched.active_progress()
        assert prog.prefix is not None
        assert set(prog.prefix) >= {
            "lookups", "hits", "hit_rate", "hit_tokens", "queried_tokens",
            "cached_pages", "evictions",
        }
        assert prog.prefix["lookups"] == 1
        # plain paged mode reports no prefix block
        plain = ContinuousBatchingScheduler(
            model, params, max_batch=2, max_len=32, kv_mode="paged",
            sync_interval=2,
        )
        assert plain.active_progress().prefix is None

    def test_prefix_cache_requires_paged_mode(self, bundle):
        _, model, params = bundle
        with pytest.raises(ValueError, match="prefix_cache requires"):
            ContinuousBatchingScheduler(
                model, params, max_batch=2, max_len=32, prefix_cache=True
            )


class TestChannelServer:
    def test_requests_over_mpsc_channel_continuous(self):
        """Two producer instances stream 2 requests each; one server instance
        drains the MPSC channel per scheduler tick, decodes them as one
        continuously-batched stream, and replies per-request on completion."""
        from repro.backends.localsim import LocalSimWorld
        from repro.frontends.channels import (
            MPSCNonLockingConsumer,
            MPSCNonLockingProducer,
            SPSCConsumer,
            SPSCProducer,
        )

        cfg = get_config("gemma3-1b", reduced=True)
        model = build(cfg)
        params, _ = model.init(jax.random.PRNGKey(0))
        MSG = 512
        PER_CLIENT = 2

        def prog(mgrs, rank):
            # NOTE: slot exchange is COLLECTIVE (paper §3.1.4) — every
            # instance participates in every tag's exchange, in the same
            # order (tag 1, 10, 11), volunteering zero slots where it is
            # not an endpoint.
            cm, mm = mgrs.communication_manager, mgrs.memory_manager
            if rank == 0:  # server
                req_cons = MPSCNonLockingConsumer(cm, mm, tag=1, capacity=4,
                                                  msg_size=MSG, n_producers=2)
                rep_prods = {
                    "c1": SPSCProducer(cm, mm, tag=10, capacity=4, msg_size=MSG),
                    "c2": SPSCProducer(cm, mm, tag=11, capacity=4, msg_size=MSG),
                }

                class Router:
                    def push(self, msg):
                        rep = json.loads(bytes(msg).rstrip(b"\0").decode())
                        rep_prods[rep["id"].split("-")[0]].push(msg)

                sched = ContinuousBatchingScheduler(model, params, max_batch=4,
                                                    max_len=32)
                server = ChannelServer(sched, req_cons, Router(), msg_size=MSG)
                server.serve(n_requests=2 * PER_CLIENT)
                return "served"
            # clients
            cidx = rank - 1
            prod = MPSCNonLockingProducer(cm, mm, tag=1, capacity=4, msg_size=MSG,
                                          producer_index=cidx)
            if cidx == 0:
                rep_cons = SPSCConsumer(cm, mm, tag=10, capacity=4, msg_size=MSG)
                cm.exchange_global_memory_slots(11, {})  # not an endpoint
            else:
                cm.exchange_global_memory_slots(10, {})  # not an endpoint
                rep_cons = SPSCConsumer(cm, mm, tag=11, capacity=4, msg_size=MSG)
            for j in range(PER_CLIENT):
                req = {"id": f"c{rank}-{j}", "prompt": [1 + rank, 2, 3, 4 + j],
                       "steps": 3 + j}
                prod.push(json.dumps(req).encode().ljust(MSG, b"\0"))
            got = {}
            while len(got) < PER_CLIENT:  # completion order, match by id
                rep = json.loads(rep_cons.pop(timeout=240).rstrip(b"\0").decode())
                assert rep["id"].startswith(f"c{rank}-")
                got[rep["id"]] = rep["tokens"]
            return got

        w = LocalSimWorld(3)
        results = w.launch(prog, timeout=300)
        assert results[0] == "served"
        for rank in (1, 2):
            assert set(results[rank]) == {f"c{rank}-{j}" for j in range(PER_CLIENT)}
            for j in range(PER_CLIENT):
                assert len(results[rank][f"c{rank}-{j}"]) == 3 + j
        w.shutdown()

    def test_oversized_reply_raises(self, bundle):
        """Satellite bugfix: an encoded reply larger than msg_size must raise
        instead of silently corrupting the ring (ljust cannot shrink)."""
        _, model, params = bundle
        sched = ContinuousBatchingScheduler(model, params, max_batch=2, max_len=64)
        server = ChannelServer(sched, consumer=None, reply_sender=None, msg_size=32)
        fin = FinishedRequest(rid="big", prompt=[1], tokens=list(range(100)),
                              finish_reason="length")
        with pytest.raises(ChannelMessageTooLargeError, match="msg_size"):
            server.encode_reply(fin)

    def test_reply_fits_is_padded(self, bundle):
        _, model, params = bundle
        sched = ContinuousBatchingScheduler(model, params, max_batch=2, max_len=64)
        server = ChannelServer(sched, consumer=None, reply_sender=None, msg_size=128)
        fin = FinishedRequest(rid="ok", prompt=[1], tokens=[1, 2, 3],
                              finish_reason="length")
        wire = server.encode_reply(fin)
        assert len(wire) == 128
        body = json.loads(wire.rstrip(b"\0").decode())
        assert body == {"id": "ok", "tokens": [1, 2, 3], "finish_reason": "length"}

    def test_request_decode_roundtrip(self):
        raw = json.dumps({"id": "x", "prompt": [1, 2], "steps": 4, "eos": 7}
                         ).encode().ljust(64, b"\0")
        req = ChannelServer.decode_request(raw)
        assert (req.rid, list(req.prompt), req.max_new_tokens, req.eos_id) == \
            ("x", [1, 2], 4, 7)

    def test_bad_requests_get_error_replies_not_crashes(self, bundle):
        """Resilience: a malformed or unservable request settles with an
        error reply instead of killing the server loop; later requests are
        still served."""
        from collections import deque

        class FakeConsumer:
            def __init__(self, msgs):
                self.msgs = deque(msgs)

            def try_pop(self):
                return self.msgs.popleft() if self.msgs else None

            def pop(self, timeout=None):
                if not self.msgs:
                    raise TimeoutError("empty")
                return self.msgs.popleft()

        class FakeReply:
            def __init__(self):
                self.out = []

            def push(self, data):
                self.out.append(json.loads(bytes(data).rstrip(b"\0").decode()))

        _, model, params = bundle
        sched = ContinuousBatchingScheduler(model, params, max_batch=2, max_len=16)
        msgs = [
            b"}{garbage".ljust(128, b"\0"),  # not JSON at all
            json.dumps({"id": "huge", "prompt": [1] * 10, "steps": 10}
                       ).encode().ljust(128, b"\0"),  # exceeds max_len
            json.dumps({"id": "good", "prompt": [1, 2, 3], "steps": 2}
                       ).encode().ljust(128, b"\0"),
        ]
        reply = FakeReply()
        ChannelServer(sched, FakeConsumer(msgs), reply, msg_size=128).serve(3)
        by_id = {r["id"]: r for r in reply.out}
        assert "bad request" in by_id[None]["error"]
        assert "cache positions" in by_id["huge"]["error"]
        assert len(by_id["good"]["tokens"]) == 2


class TestServeIngestDiscipline:
    def test_full_backlog_does_not_consume_channel_messages(self, bundle):
        """Regression: with the backlog at max_batch, the ingest loop must
        not poll the arrival future — done() pops the ring as a side effect
        and the message would be dropped when serve() returns."""
        from collections import deque

        class CountingConsumer:
            def __init__(self, msgs):
                self.msgs = deque(msgs)

            def try_pop(self):
                return self.msgs.popleft() if self.msgs else None

        class FakeReply:
            def __init__(self):
                self.out = []

            def push(self, data):
                self.out.append(json.loads(bytes(data).rstrip(b"\0").decode()))

        _, model, params = bundle
        msgs = [
            json.dumps({"id": f"q{i}", "prompt": [1, 2, 3], "steps": 4}
                       ).encode().ljust(256, b"\0")
            for i in range(3)
        ]
        cons = CountingConsumer(msgs)
        sched = ContinuousBatchingScheduler(model, params, max_batch=1, max_len=32)
        ChannelServer(sched, cons, FakeReply(), msg_size=256).serve(1)
        # exactly one request was settled; the others must still be queued
        assert len(cons.msgs) >= 1, "undrained requests were consumed and lost"

    def test_idle_timeout_surfaces_instead_of_hanging(self, bundle):
        """A server idle past idle_timeout with requests still awaited
        raises a (catchable) TimeoutError rather than spinning forever."""
        _, model, params = bundle

        class EmptyConsumer:
            def try_pop(self):
                return None

        sched = ContinuousBatchingScheduler(model, params, max_batch=1, max_len=32)
        server = ChannelServer(sched, EmptyConsumer(), None, idle_timeout=0.05)
        with pytest.raises(TimeoutError, match="no request arrived"):
            server.serve(1)


class TestStreamingReplies:
    def test_streaming_over_localsim_fabric(self, bundle):
        """Acceptance scenario: one client, one server over the localsim
        fabric, a >= 16-token request served with stream_interval=4 — the
        client observes >= 2 delta chunks BEFORE the terminal chunk, and the
        deltas reassemble (in arrival order) to the full token list."""
        from repro.backends.localsim import LocalSimWorld
        from repro.frontends.channels import SPSCConsumer, SPSCProducer

        _, model, params = bundle
        MSG = 512
        STEPS = 18

        def prog(mgrs, rank):
            cm, mm = mgrs.communication_manager, mgrs.memory_manager
            if rank == 0:  # server
                req_cons = SPSCConsumer(cm, mm, tag=1, capacity=4, msg_size=MSG)
                rep_prod = SPSCProducer(cm, mm, tag=10, capacity=16, msg_size=MSG)

                class Reply:
                    def push(self, msg):
                        rep_prod.push(msg)

                sched = ContinuousBatchingScheduler(model, params, max_batch=2,
                                                    max_len=32)
                ChannelServer(sched, req_cons, Reply(), msg_size=MSG,
                              stream_interval=4).serve(n_requests=1)
                return "served"
            # client
            req_prod = SPSCProducer(cm, mm, tag=1, capacity=4, msg_size=MSG)
            rep_cons = SPSCConsumer(cm, mm, tag=10, capacity=16, msg_size=MSG)
            req = {"id": "s-0", "prompt": [1, 2, 3, 4], "steps": STEPS}
            req_prod.push(json.dumps(req).encode().ljust(MSG, b"\0"))
            chunks = []
            while True:
                chunk = json.loads(rep_cons.pop(timeout=240).rstrip(b"\0").decode())
                chunks.append(chunk)
                if chunk["done"]:
                    return chunks

        w = LocalSimWorld(2)
        results = w.launch(prog, timeout=300)
        w.shutdown()
        chunks = results[1]
        assert results[0] == "served"
        # every chunk belongs to the request; only the last is terminal
        assert all(c["id"] == "s-0" for c in chunks)
        assert [c["done"] for c in chunks[:-1]] == [False] * (len(chunks) - 1)
        assert chunks[-1]["done"] is True
        assert len(chunks) - 1 >= 2, f"want >=2 deltas before terminal: {chunks}"
        assert chunks[-1]["finish_reason"] == "length"
        tokens = [t for c in chunks for t in c["delta"]]
        assert len(tokens) == STEPS

    def test_stream_reassembly_matches_terse_protocol(self, bundle):
        """Streaming is a transport change only: per-request delta
        concatenation equals the terse protocol's token list, interleaved
        ids notwithstanding."""
        from collections import deque

        class FakeConsumer:
            def __init__(self, msgs):
                self.msgs = deque(msgs)

            def try_pop(self):
                return self.msgs.popleft() if self.msgs else None

        class FakeReply:
            def __init__(self):
                self.out = []

            def push(self, data):
                self.out.append(json.loads(bytes(data).rstrip(b"\0").decode()))

        _, model, params = bundle
        reqs = [
            {"id": "a", "prompt": [1, 2, 3], "steps": 9},
            {"id": "b", "prompt": [4, 5, 6, 7], "steps": 6},
        ]
        msgs = [json.dumps(r).encode().ljust(256, b"\0") for r in reqs]

        sched = ContinuousBatchingScheduler(model, params, max_batch=2, max_len=32)
        terse = FakeReply()
        ChannelServer(sched, FakeConsumer(list(msgs)), terse, msg_size=256).serve(2)
        expected = {r["id"]: r["tokens"] for r in terse.out}

        sched2 = ContinuousBatchingScheduler(model, params, max_batch=2, max_len=32)
        streamed = FakeReply()
        ChannelServer(sched2, FakeConsumer(list(msgs)), streamed, msg_size=256,
                      stream_interval=2).serve(2)
        got: dict = {}
        finish: dict = {}
        for chunk in streamed.out:
            assert set(chunk) >= {"id", "delta", "done"}
            assert chunk["id"] not in finish, "chunk after terminal chunk"
            got.setdefault(chunk["id"], []).extend(chunk["delta"])
            if chunk["done"]:
                finish[chunk["id"]] = chunk["finish_reason"]
        assert got == expected
        assert finish == {"a": "length", "b": "length"}
        # both requests decoded long enough to produce intermediate deltas
        deltas_before_done = {"a": 0, "b": 0}
        seen_done = set()
        for chunk in streamed.out:
            if chunk["done"]:
                seen_done.add(chunk["id"])
            elif chunk["id"] not in seen_done:
                deltas_before_done[chunk["id"]] += 1
        assert deltas_before_done["a"] >= 2

    def test_single_token_request_streams_terminal_only(self, bundle):
        from collections import deque

        class FakeConsumer:
            def __init__(self, msgs):
                self.msgs = deque(msgs)

            def try_pop(self):
                return self.msgs.popleft() if self.msgs else None

        class FakeReply:
            def __init__(self):
                self.out = []

            def push(self, data):
                self.out.append(json.loads(bytes(data).rstrip(b"\0").decode()))

        _, model, params = bundle
        sched = ContinuousBatchingScheduler(model, params, max_batch=2, max_len=32)
        reply = FakeReply()
        msg = json.dumps({"id": "one", "prompt": [5, 6], "steps": 1}
                         ).encode().ljust(256, b"\0")
        ChannelServer(sched, FakeConsumer([msg]), reply, msg_size=256,
                      stream_interval=1).serve(1)
        assert len(reply.out) == 1
        chunk = reply.out[0]
        assert chunk["done"] is True and len(chunk["delta"]) == 1

    def test_stream_interval_validation(self, bundle):
        _, model, params = bundle
        sched = ContinuousBatchingScheduler(model, params, max_batch=2, max_len=32)
        with pytest.raises(ValueError, match="stream_interval"):
            ChannelServer(sched, None, None, stream_interval=0)


class TestSchedulerServeDriver:
    def test_duplicate_rids_do_not_hang(self, bundle):
        """serve() terminates by finish count, not distinct rids."""
        _, model, params = bundle
        sched = ContinuousBatchingScheduler(model, params, max_batch=2, max_len=64)
        twins = [
            Request(rid="same", prompt=[1, 2, 3], max_new_tokens=1),
            Request(rid="same", prompt=[4, 5, 6], max_new_tokens=1),
        ]
        results = sched.serve(twins)  # both finish at prefill; keyed dict keeps one
        assert set(results) == {"same"} and len(results["same"].tokens) == 1
