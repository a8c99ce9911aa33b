"""The chip benchmark's arithmetic, on the CPU: trace reduction, operation
and byte counts, traffic generation, and the files that define the cells."""
from __future__ import annotations

import collections
import hashlib
import json
import sys
import types
from pathlib import Path

import numpy as np
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


def small_cell(program=SMALL, reference=None) -> harness.Cell:
    """A cell of `program` whose configuration names `reference` (or none:
    the default, `refmodel`)."""
    config = {"name": "small", "program": program}
    if reference is not None:
        config["reference"] = reference
    return harness.Cell(name="small", chips=1, config=config, traffic={}, serve={})


def test_matmul_params_by_hand():
    # per layer: q 8*4*2 + k,v 2*8*2*2 + o 4*2*8 = 64+64+64 = 192; mlp 2*8*16 = 256
    assert work.matmul_params(small_cell()) == 2 * (192 + 256) + 8 * 10
    gated = small_cell(dict(SMALL, act="silu"))
    assert work.matmul_params(gated) == 2 * (192 + 384) + 8 * 10


def test_attention_work_by_hand():
    cell = small_cell()
    # per layer per token at ctx 5: 4 * H * hd * ctx = 4*4*2*5 = 160 flops
    assert work.attn_flops(cell, 5) == 2 * 160
    # bytes: K and V of 5 positions * KV 2 * hd 2 * 2 B = 80, q and o 2*4*2*2 = 32
    assert work.attn_bytes(cell, 5) == 2 * (80 + 32)
    assert work.decode_flops(cell, 5) == 2 * work.matmul_params(cell) + 2 * 160


def test_counts_come_from_the_configurations_reference():
    dense = small_cell(reference="refmodel")
    assert dense.reference is small_cell().reference is harness.load_reference("refmodel")
    assert work.decode_flops(dense, 7) == dense.reference.decode_flops(SMALL, 7)
    # a reference whose block has other counts
    other = small_cell()
    other.reference = types.SimpleNamespace(matmul_params=lambda p: 11,
                                            decode_flops=lambda p, ctx: 22 + ctx)
    assert work.matmul_params(other) == 11 and work.decode_flops(other, 3) == 25
    # ... and one that counts nothing: no dense count stands in
    assert work.attn_flops(other, 3) is None and work.attn_bytes(other, 3) is None


def _counted_run(reference) -> harness.Run:
    """A window of two decode steps and a traced kernel, for the count readers."""
    cell = small_cell()
    if reference is not None:
        cell.reference = reference
    steps = [harness.Step(0.1, 0.2, 8, [(5, 8), (9, 4)]), harness.Step(0.3, 0.5, 8, [(13, 8)])]
    return harness.Run(
        cell=cell, peaks={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e8}, setup_s=1.0,
        t_open=0.0, t_close=1.0, requests={}, steps=steps, admits=[], prefix_open={},
        prefix_close={}, trace={"op_s": {"paged_decode_attention.1": 1e-3}},
        trace_span=(0.0, 1.0))


def test_count_readers_by_hand_and_with_no_counts():
    run = _counted_run(None)
    ctxs = [5 + i for i in range(8)] + [9 + i for i in range(4)] + [13 + i for i in range(8)]
    # attention per token: L 2 * 4 * H 4 * hd 2 * ctx
    flops = sum(2 * work.matmul_params(run.cell) + 2 * 4 * 4 * 2 * c for c in ctxs)
    assert harness.reader("decode_mfu")(run) == pytest.approx(100 * flops / (0.3 * 1e9))
    attn = sum(work.attn_flops(run.cell, c) for c in ctxs)
    nbytes = sum(work.attn_bytes(run.cell, c) for c in ctxs)
    assert harness.reader("paged_attn_roofline")(run) == pytest.approx(
        100 * max(attn / 1e9, nbytes / 1e8) / 1e-3)
    silent = _counted_run(types.SimpleNamespace())
    assert harness.reader("decode_mfu")(silent) is None
    assert harness.reader("paged_attn_roofline")(silent) is None


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
    a = traffic_gen.schedule(t, rate=3.0, pre=5, seconds=55, seed=seed, vocab=49152)
    b = traffic_gen.schedule(t, rate=3.0, pre=5, seconds=55, seed=seed, vocab=49152)
    c = traffic_gen.schedule(t, rate=3.0, pre=5, seconds=55, seed=seed + 1, vocab=49152)
    assert [(i.due, i.prompt, i.max_new) for i in a] == [(i.due, i.prompt, i.max_new) for i in b]
    assert [i.prompt for i in a] != [i.prompt for i in c]
    # another seed: the same sizes in the window, in another order
    wa, wc = ([i for i in x if i.due >= 0.0] for x in (a, c))
    assert sorted(len(i.prompt) for i in wa) == sorted(len(i.prompt) for i in wc)
    assert [len(i.prompt) for i in wa] != [len(i.prompt) for i in wc]
    assert sorted(i.max_new for i in wa) == sorted(i.max_new for i in wc)
    assert wa[0].due == 0.0 and a[0].due >= -5.0
    profile = t.get("rate_profile", [[1.0, 1.0]])
    assert len(wa) == round(traffic_gen.arrivals(55.0, 3.0, profile))
    assert all(x.due < y.due for x, y in zip(a, a[1:]))


@pytest.mark.parametrize("pre", [5.0, 20.0, 70.0])
@pytest.mark.parametrize("name", sorted(MIXES))
def test_the_seconds_before_the_window_replay_its_close(name, pre):
    """Before the window come the window's last `pre` seconds again (the
    whole window, further back, where `pre` is longer): the same gaps,
    prompt and output lengths, on prompts of their own."""
    items = traffic_gen.schedule(MIXES[name](), rate=2.8, pre=pre, seconds=50.0,
                                 seed=2**31 + 17, vocab=49152)
    window = [i for i in items if i.due >= 0.0]
    before = [i for i in items if i.due < 0.0]
    replayed = [(round(i.due - k * 50.0, 9), len(i.prompt), i.max_new)
                for k in (2, 1) for i in window if i.due - k * 50.0 >= -pre]
    assert [(round(i.due, 9), len(i.prompt), i.max_new) for i in before] == replayed
    assert len(before) > 0
    assert not {i.prompt[0] for i in before} & {i.prompt[0] for i in window}


@pytest.mark.parametrize("seed", [5, 2**31 + 19])
def test_a_steady_server_outputs_the_window_s_own_tokens(seed):
    """Served at a fixed time to the first token and between tokens, the
    tokens output while the window is open are those of the requests due
    in it, on every seed: what runs at the open replays what runs at the
    close, since no request outlives the 20 s before the window."""
    chat = traffic_gen.load("chat")
    items = traffic_gen.schedule(chat, rate=2.8, pre=20.0, seconds=50.0, seed=seed,
                                 vocab=49152)
    first, gap = 0.3, 0.035  # s; 0.3 + 512 * 0.035 < 20
    out = 0.0
    for i in items:
        start = i.due + first
        out += max(0.0, min(start + i.max_new * gap, 50.0) - max(start, 0.0)) / gap
    assert out == pytest.approx(sum(i.max_new for i in items if i.due >= 0.0), rel=1e-9)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_every_prompt_length_is_a_warmed_tail(name):
    t = MIXES[name]()
    tails = set(traffic_gen.tail_lengths(t))
    items = traffic_gen.schedule(t, rate=5.0, pre=10, seconds=50, seed=3, vocab=49152)
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
                                     rate=rate, pre=0, seconds=n / rate, seed=seed, vocab=4096)
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


def _window(traffic, seed, pre=20.0, seconds=50.0):
    items = traffic_gen.schedule(traffic, rate=2.8, pre=pre, seconds=seconds, seed=seed,
                                 vocab=49152)
    return [i for i in items if 0.0 <= i.due < seconds]


@pytest.mark.parametrize("seed", [1, 2**31 + 11])
@pytest.mark.parametrize("name", sorted(MIXES))
def test_the_window_holds_the_same_work_on_every_seed(name, seed):
    """The requests due in the window are one set on every seed: their
    number, prompts' and outputs' lengths, and at a steady rate their gaps
    (less the last, to the close), in another order."""
    import numpy as np

    t = MIXES[name]()
    a, b = _window(t, seed), _window(t, seed + 1)
    profile = t.get("rate_profile", [[1.0, 1.0]])
    expected = traffic_gen.arrivals(50.0, 2.8, profile)
    assert len(a) == len(b) == round(expected) and a[0].due == b[0].due == 0.0
    assert sorted(len(i.prompt) for i in a) == sorted(len(i.prompt) for i in b)
    assert sorted(i.max_new for i in a) == sorted(i.max_new for i in b)
    assert [i.max_new for i in a] != [i.max_new for i in b]
    if "rate_profile" not in t:  # a profile maps the same gaps by their phase
        gaps_a, gaps_b = (np.sort(np.round(np.diff([i.due for i in x]), 9)) for x in (a, b))
        assert len(np.intersect1d(gaps_a, gaps_b)) >= len(a) - 3


@pytest.mark.parametrize("seed", [3, 2**31 + 13])
def test_each_stretch_of_the_window_gets_an_even_share(seed):
    """Dealt over the window's five stretches of 10 s, each fifth of its
    requests holds a fifth of the output and prompt tokens to within a
    few percent, and each 10 s about a fifth of the arrivals."""
    import numpy as np

    chat = traffic_gen.load("chat")
    w = _window(chat, seed)
    for size in ([i.max_new for i in w], [len(i.prompt) for i in w]):
        fifths = np.array([sum(x) for x in np.split(np.array(size), 5)])
        assert np.all(np.abs(fifths / fifths.mean() - 1) < 0.04)
    counts = np.histogram([i.due for i in w], bins=5, range=(0.0, 50.0))[0]
    assert np.all(np.abs(counts - 28) <= 3)


def test_rescale_is_plain_scaling_for_a_constant_rate():
    import numpy as np

    s = np.array([0.0, 1.0, 2.5, 7.0])
    assert traffic_gen.rescale(s, 2.0, [[1.0, 1.0]]) == pytest.approx(s / 2.0)


def test_burst_profile_packs_arrivals_into_the_on_phase():
    import numpy as np

    items = traffic_gen.schedule(BURST, rate=2.0, pre=0, seconds=600, seed=4, vocab=4096)
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


# -- what granite-20b.chat reads, pinned ------------------------------------------


def _sha(obj) -> str:
    return hashlib.sha256(json.dumps(obj).encode()).hexdigest()


def _items_sha(items) -> str:
    return _sha([[i.due, i.rid, i.prompt, i.max_new] for i in items])


#: digests of the chat mix at the cell's rate 2.8, 20 s before a 50 s
#: window: the schedule (dues, ids, prompts, output lengths) as the
#: generator makes it with a fixed block of work for the window, dealt
#: over its stretches, and its last 20 s replayed before it; and the
#: warm-up, as the generator made it before documents could be shared
CHAT_DIGESTS = {
    0: ("6b0961d1d8b6c2ac00e773a7e866a166a91cecd86af316222225bca12d1aa165",
        "30bc1a644c80f8e83c2d20a01d0d70b9a8f148e739696a5b23869d11504196a5"),
    12345: ("9094ccfcfcf8ac1856fd220add0a2fb3ea52787bbe6e97fe63d22ae6d9ab4d4c",
            "b683fd1afc2e52f2610fadff8c8f98e34ac2e5058aef0c5c978a56ef264c2e3c"),
    2**31 + 7: ("d38ab11831368630ba40d551a2b3452baedeef8e326fa8aa37079fea09409737",
                "1c4372164ce8856c715537acc017de8dbd3dd724d5b4c2aea88d851e2f931221"),
}


@pytest.mark.parametrize("seed", sorted(CHAT_DIGESTS))
def test_chat_schedule_and_warm_up_are_pinned(seed):
    chat = traffic_gen.load("chat")
    items = traffic_gen.schedule(chat, rate=2.8, pre=20.0, seconds=50.0, seed=seed, vocab=49152)
    assert _items_sha(items) == CHAT_DIGESTS[seed][0]
    assert _items_sha(traffic_gen.warm_items(chat, 49152, seed)) == CHAT_DIGESTS[seed][1]


#: digests of the default reference's weights for the tiny programs of
#: test_chipbench_run.py (leaf paths, shapes, dtypes and bytes)
WEIGHT_DIGESTS = {
    ("gelu", 7): "f07770581345f76844fd5edf83f08d858a5e1b75fde5d94ca5ae281f31c529c6",
    ("gelu", 2**31 + 7): "79499c55cf3518bbb78c9ca72a902ac49fdfca592c40271550feb53515e03d2c",
    ("silu", 7): "143c44ec50961f2f62798b7818ed421e58c1422f2e2219968295ad64351f27b8",
    ("silu", 2**31 + 7): "c52ba465006813768bf9192fd8446a9749cf8b1b8aa93ff06e207a3b3a70a4bd",
}


@pytest.mark.parametrize("act, seed", sorted(WEIGHT_DIGESTS))
def test_default_reference_weights_are_pinned(act, seed):
    import jax
    import numpy as np

    gated = act == "silu"
    prog = dict(family="dense", num_layers=2, d_model=128, num_heads=4,
                num_kv_heads=2 if gated else 1, d_ff=256, vocab_size=1024,
                max_seq_len=512, act=act, rope_theta=10000.0, norm_eps=1e-6,
                tie_embeddings=False, param_dtype="bfloat16", compute_dtype="bfloat16")
    params = small_cell(prog).reference.make_weights(prog, seed)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    assert _sha([[jax.tree_util.keystr(k), list(v.shape), str(v.dtype),
                  hashlib.sha256(np.asarray(v).tobytes()).hexdigest()]
                 for k, v in leaves]) == WEIGHT_DIGESTS[(act, seed)]


# -- documents asked more than once ------------------------------------------------

#: documents of two lengths (one not a whole number of 16-token pages), each
#: asked 2 or 3 times, beside unshared prompts
DOCS = {"prompt_lengths": [24, 30],
        "shared": {"documents": [32, 41], "asks": [2, 3], "questions": [16, 21, 17]},
        "output": {"dist": "uniform", "min": 4, "max": 12}}


def _lcp(a, b) -> int:
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


def _by_first_token(items) -> dict:
    groups = collections.defaultdict(list)
    for it in items:
        groups[it.prompt[0]].append(it.prompt)
    return groups


def _anatomy(items, traffic) -> tuple:
    """(document length, asks) of each document, the question lengths, and
    the unshared prompt lengths, read from the prompts alone: prompts that
    open with one token form a group, and a group's common prefix is its
    document."""
    docs, questions, unshared = [], [], []
    for prompts in _by_first_token(items).values():
        if len(prompts) == 1 and len(prompts[0]) in traffic.get("prompt_lengths", ()):
            unshared.append(len(prompts[0]))
            continue
        d = min(_lcp(prompts[0], p) for p in prompts[1:])
        docs.append((d, len(prompts)))
        questions += [len(p) - d for p in prompts]
        # the asks' questions open with tokens of their own
        assert len({p[d] for p in prompts}) == len(prompts)
    return sorted(docs), sorted(questions), sorted(unshared)


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_documents_keep_their_multiset_across_seeds(seed):
    a = traffic_gen.schedule(DOCS, rate=3.0, pre=5, seconds=25, seed=seed, vocab=4096)
    again = traffic_gen.schedule(DOCS, rate=3.0, pre=5, seconds=25, seed=seed, vocab=4096)
    b = traffic_gen.schedule(DOCS, rate=3.0, pre=5, seconds=25, seed=seed + 1, vocab=4096)
    assert _items_sha(a) == _items_sha(again)
    # another seed: the same documents, asks, questions, prompts, outputs
    # and gaps (less the one before the first due) in the window, in
    # another order
    wa, wb = ([i for i in x if i.due >= 0.0] for x in (a, b))
    assert _anatomy(wa, DOCS) == _anatomy(wb, DOCS)
    assert sorted(i.max_new for i in wa) == sorted(i.max_new for i in wb)
    gaps_a, gaps_b = (np.round(np.diff([i.due for i in x]), 9) for x in (wa, wb))
    assert len(np.intersect1d(gaps_a, gaps_b)) >= len(wa) - 3
    assert [len(i.prompt) for i in wa] != [len(i.prompt) for i in wb]
    assert a[0].due >= -5.0 and all(x.due < y.due for x, y in zip(a, a[1:]))


def test_each_document_is_asked_its_count():
    items = traffic_gen.schedule(DOCS, rate=3.0, pre=5, seconds=25, seed=9, vocab=4096)
    window = [i for i in items if i.due >= 0.0]
    docs, questions, unshared = _anatomy(window, DOCS)
    # cycles of 12 (24, 30, and documents 32 and 41 asked 2 and 3 times),
    # from the first: 75 requests asked for in the window (6 whole cycles,
    # then 24, 30, (32, 2))
    assert len(window) == 76
    assert collections.Counter(docs) == {(32, 2): 7, (32, 3): 6, (41, 2): 6, (41, 3): 6}
    assert collections.Counter(unshared) == {24: 7, 30: 7}
    # the asks take the question lengths in turn: 62 of them
    assert collections.Counter(questions) == {16: 21, 21: 21, 17: 20}
    # a document replayed before the window is asked as often as its copy
    # in the window's last 5 s
    tail = collections.Counter(i.prompt[0] for i in window if i.due >= 20.0)
    before = collections.Counter(i.prompt[0] for i in items if i.due < 0.0)
    assert sorted(tail.values()) == sorted(before.values())
    out = DOCS["output"]
    assert all(out["min"] <= i.max_new <= out["max"] for i in items)


def test_no_two_documents_or_prompts_share_a_first_token():
    items = traffic_gen.schedule(DOCS, rate=3.0, pre=5, seconds=25, seed=11, vocab=4096)
    window = [i for i in items if i.due >= 0.0]
    # one group per document and per unshared prompt: 39 in the window
    # (section above), and one for each replayed from its last 5 s
    replayed = len(_by_first_token([i for i in window if i.due >= 20.0]))
    assert len(_by_first_token(window)) == 39
    assert len(_by_first_token(items)) == 39 + replayed
    docs, _, unshared = _anatomy(window, DOCS)
    assert {d for d, _ in docs} == {32, 41} and set(unshared) == {24, 30}


def _tails(prompts) -> list:
    """Prefill tails of prompts served in turn by a cache that holds every
    earlier prompt: each prompt less its longest match, kept below its length."""
    out = []
    for i, p in enumerate(prompts):
        hit = max((_lcp(p, q) for q in prompts[:i]), default=0)
        out.append(len(p) - min(hit, len(p) - 1))
    return out


@pytest.mark.parametrize("name", ["docs", "docs-alone"])
def test_warm_up_runs_every_miss_and_hit_tail(name):
    traffic = DOCS if name == "docs" else {k: v for k, v in DOCS.items() if k != "prompt_lengths"}
    warm = traffic_gen.warm_items(traffic, 4096, 5)
    warmed = _tails([w.prompt for w in warm])
    assert sorted(set(warmed)) == traffic_gen.tail_lengths(traffic)
    items = traffic_gen.schedule(traffic, rate=3.0, pre=5, seconds=25, seed=5, vocab=4096)
    hits = _tails([i.prompt for i in items])  # every earlier ask finished and cached
    misses = [len(i.prompt) for i in items]  # nothing cached
    assert set(hits) | set(misses) <= set(warmed)
    assert set(hits) & set(DOCS["shared"]["questions"])  # the mix does hit


def test_a_burst_profile_keeps_the_shared_mix():
    # a window of whole periods of the profile expects as many arrivals as
    # at the steady rate
    burst = dict(DOCS, rate_profile=[[5.0, 2.5], [10.0, 0.25]])
    a, b = ([i for i in traffic_gen.schedule(t, rate=3.0, pre=15, seconds=30, seed=4,
                                             vocab=4096) if i.due >= 0.0]
            for t in (DOCS, burst))
    assert _anatomy(a, DOCS) == _anatomy(b, DOCS)
    assert [(len(i.prompt), i.max_new) for i in a] == [(len(i.prompt), i.max_new) for i in b]
    assert [i.due for i in a] != [i.due for i in b]


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
