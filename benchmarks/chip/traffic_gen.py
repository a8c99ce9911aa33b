"""The one traffic generator: a traffic file plus a cell's rate -> a schedule.

A traffic file (``traffic/<name>.json``) holds only parameters, and every
mix is made by the same code from them:

* ``prompt_lengths``: a table of prompt lengths, each entry used equally
  often (the program compiles one prefill per distinct length, so the set
  is fixed and warmed before the window);
* ``output``: the distribution of output lengths (``lognormal`` with
  ``median`` and ``sigma``, or ``uniform``), clipped to ``min``..``max``;
* ``rate_profile``: ``[[seconds, relative rate], ...]``, cycled, for
  arrivals whose rate changes over time (on/off bursts); omitted, the rate
  is constant;
* ``shared``: documents asked more than once, ``{"documents": [lengths],
  "asks": count or [counts], "questions": [lengths]}``. Each ask is its
  document followed by a question of its own.

The requests come in cycles: every entry of ``prompt_lengths``, where the
file has it, once as an unshared prompt, and every pair of a document
length and an ask count once (a document of that length asked that many
times). The asks take the question lengths in turn. The units of the
cycles are taken in that order, from the first, until they hold at least
as many requests as the rate and the window's length ask for, so every
document is asked its whole count within the window. A document replayed
before the window is a document of its own, asked as often as its copy
is asked in the window's last ``pre`` seconds.

Arrivals are open loop and Poisson at the cell's rate, shaped by the
profile: unit-rate exponential gaps are mapped through the inverse of the
profile's cumulative rate (time rescaling), so a burst packs the same gaps
closer. The window is one block of requests, gaps (summing to its length)
and output lengths, so every seed gets the same work in it. Its gaps,
requests and output lengths are each dealt over its stretches of about
``STRETCH_S`` seconds: every stretch gets an even share of the small and
the large ones, in an order drawn from the seed. Each position then has
the distribution's own marginal, and runs of short gaps come within a
stretch as they do in a Poisson stream, while the work in each part of
the window does not change from seed to seed. The ``pre`` seconds before
the window replay its last ``pre`` seconds, so that the work running at
the open is the work running at the close.

Every unshared prompt and every document opens with a first token that no
other of them opens with, and no two asks of one document open their
questions with the same token, so the radix cache shares nothing by
accident: a partial match of one token more would make a tail length
outside the table. An ask finds its whole document cached where an earlier
ask has finished and its pages are still held; its tail is then the
question, or, where the page holding the document's last token was not
full when cached (a question and answer shorter than a page), the
question and that page's share of the document. The warm-up serves a miss
and then a hit of every pair of document and question length, so it
compiles the tails the program makes of them.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import json
import math
import statistics
from pathlib import Path
from typing import List

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Item:
    due: float  # seconds from the window's start (negative: before it)
    rid: str
    prompt: List[int]
    max_new: int


def load(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def quantiles(spec: dict, n: int) -> List[int]:
    """n quantiles ``(i + 0.5) / n`` of a length distribution, clipped, in
    increasing order."""
    qs = [(i + 0.5) / n for i in range(n)]
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "lognormal":
        nd = statistics.NormalDist()
        vals = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(q)) for q in qs]
    elif spec["dist"] == "uniform":
        vals = [lo + (hi - lo) * q for q in qs]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return [int(round(min(hi, max(lo, v)))) for v in vals]


def _profile(rate: float, profile) -> tuple:
    """One period of the cycled `profile` ([[seconds, relative rate], ...],
    normalised to a mean of 1) at mean rate `rate`: its length, the times of
    its phase boundaries, the expected arrivals by each, and each phase's
    relative rate."""
    durs = np.array([p[0] for p in profile], float)
    rel = np.array([p[1] for p in profile], float)
    rel = rel * durs.sum() / (durs * rel).sum()
    bounds = np.concatenate([[0.0], np.cumsum(durs)])
    lam = np.concatenate([[0.0], np.cumsum(durs * rel * rate)])
    return bounds[-1], bounds, lam, rel


def arrivals(t: float, rate: float, profile) -> float:
    """Expected arrivals by time `t` of a Poisson stream of mean rate `rate`
    shaped by the cycled `profile`."""
    period, bounds, lam, rel = _profile(rate, profile)
    k, r = divmod(t, period)
    j = min(bisect.bisect_right(bounds, r) - 1, len(rel) - 1)
    return k * lam[-1] + lam[j] + (r - bounds[j]) * rel[j] * rate


def rescale(s: np.ndarray, rate: float, profile) -> np.ndarray:
    """Times at which a Poisson stream of mean rate `rate`, shaped by the
    cycled `profile`, reaches the unit-rate arrival times `s`: the inverse
    of `arrivals`."""
    period, bounds, lam, rel = _profile(rate, profile)
    out = np.empty_like(s)
    for i, v in enumerate(s):
        k, r = divmod(v, lam[-1])
        j = min(bisect.bisect_right(lam, r) - 1, len(rel) - 1)
        out[i] = k * period + bounds[j] + (r - lam[j]) / (rel[j] * rate)
    return out


def tail_lengths(traffic: dict) -> List[int]:
    """Every prefill tail length the mix can produce: an unshared prompt
    whole; an ask, the document and its question on a miss, the question
    alone on a hit."""
    sh = traffic.get("shared", {})
    documents, questions = sh.get("documents", ()), sh.get("questions", ())
    return sorted(set(traffic.get("prompt_lengths", ()))
                  | {d + q for d in documents for q in questions} | set(questions))


def _prompts(rng, lengths, vocab: int) -> List[List[int]]:
    firsts = rng.choice(np.arange(1, vocab), size=len(lengths), replace=False)
    return [[int(f)] + rng.integers(1, vocab, int(n) - 1).tolist()
            for f, n in zip(firsts, lengths)]


def _requests(traffic: dict, n: int) -> tuple:
    """Units of the mix's cycles, up to the one that brings the count of
    requests to `n` or more. Returns the lengths of the documents and
    unshared prompts (the heads), and the requests as ``(head, question
    length)``, with 0 for an unshared prompt."""
    sh = traffic.get("shared", {})
    asks = sh.get("asks", [])
    asks = [asks] if isinstance(asks, int) else asks
    cycle = ([(length, 0) for length in traffic.get("prompt_lengths", ())]
             + [(d, a) for d in sh.get("documents", ()) for a in asks])
    heads: List[int] = []
    requests: List[tuple] = []
    n_asked = 0
    while len(requests) < n:
        length, k = cycle[len(heads) % len(cycle)]
        if not k:
            requests.append((len(heads), 0))
        for _ in range(k):
            requests.append((len(heads), sh["questions"][n_asked % len(sh["questions"])]))
            n_asked += 1
        heads.append(length)
    return heads, requests


def _request_prompts(rng, heads: List[int], requests, order, vocab: int) -> List[List[int]]:
    """Token ids of the requests taken in `order`: each head opens with a
    token of its own (the heads' texts drawn in the order of their first
    request), and the asks of one document open their questions with
    tokens that differ from each other."""
    first_asked = list(dict.fromkeys(requests[i][0] for i in order))
    texts = dict(zip(first_asked, _prompts(rng, [heads[h] for h in first_asked], vocab)))
    n_asks = collections.Counter(h for h, q in requests if q)
    q_firsts = {h: iter(rng.choice(np.arange(1, vocab), size=k, replace=False).tolist())
                for h, k in sorted(n_asks.items())}
    prompts = []
    for i in order:
        h, q = requests[i]
        question = ([next(q_firsts[h])] + rng.integers(1, vocab, q - 1).tolist()) if q else []
        prompts.append(texts[h] + question)
    return prompts


#: the length of the stretches of the window over which its work is dealt evenly
STRETCH_S = 10.0


def _deal(rng, ranked, parts: int) -> np.ndarray:
    """`ranked` (given in increasing order of size) in an order drawn from
    `rng` in which each of `parts` consecutive stretches holds an even
    share of it: taken `parts` at a time, each goes to another stretch,
    chosen at random; each stretch is then shuffled."""
    stretches: List[list] = [[] for _ in range(parts)]
    for r in range(0, len(ranked), parts):
        for j, v in zip(rng.permutation(parts), ranked[r:r + parts]):
            stretches[j].append(v)
    return np.concatenate([rng.permutation(s) for s in stretches])


def schedule(traffic: dict, *, rate: float, pre: float, seconds: float, seed: int,
             vocab: int) -> List[Item]:
    """Requests due over the ``pre`` seconds before the window (due < 0) and
    the ``seconds`` of the window; ``rate`` is the mean in requests per
    second. The window is one block with a fixed set of requests, gaps and
    output lengths, so the work due in it is the same on every seed; its
    gaps sum to its length, so its first request is due at its open. The
    ``pre`` seconds replay the window's last ``pre`` seconds (the whole
    window again, further back, where ``pre`` is longer): the same dues
    less the window's length, prompt and output lengths, on heads of their
    own. What is still running at the open is then what is still running
    at the close, and the tokens output in the window are on every seed
    those of the window's own requests."""
    profile = traffic.get("rate_profile", [[1.0, 1.0]])
    rng = np.random.default_rng(seed)
    u = arrivals(seconds, rate, profile)
    heads, requests = _requests(traffic, max(1, int(round(u))))
    n = len(requests)
    parts = max(1, min(n, int(round(seconds / STRETCH_S))))
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    gaps = _deal(rng, np.sort(gaps) * u / gaps.sum(), parts)
    dues = rescale(np.cumsum(gaps) - gaps[0], rate, profile)
    by_length = sorted(range(n), key=lambda i: heads[requests[i][0]] + requests[i][1])
    order = _deal(rng, by_length, parts)
    outs = _deal(rng, quantiles(traffic["output"], n), parts)
    window = [(float(dues[i]), requests[order[i]], int(outs[i])) for i in range(n)]
    rows, copies = [], {}
    for k in range(math.ceil(pre / seconds), 0, -1):
        for due, (h, q), out in window:
            if due - k * seconds >= -pre:
                if (k, h) not in copies:
                    copies[(k, h)] = len(heads)
                    heads.append(heads[h])
                rows.append((due - k * seconds, (copies[(k, h)], q), out))
    rows += window
    prompts = _request_prompts(rng, heads, [r for _, r, _ in rows], range(len(rows)), vocab)
    return [Item(due, f"r{i}", p, out) for i, ((due, _, out), p) in enumerate(zip(rows, prompts))]


def warm_items(traffic: dict, vocab: int, seed: int) -> List[Item]:
    """Requests that, served one after another, run every prefill tail
    length the mix can produce through `try_admit`: each unshared length
    once, and for each pair of document and question length a new document
    asked twice, a miss and then a hit."""
    sh = traffic.get("shared", {})
    unshared = sorted(set(traffic.get("prompt_lengths", ())))
    pairs = [(d, q) for d in sorted(set(sh.get("documents", ())))
             for q in sorted(set(sh.get("questions", ())))]
    heads = unshared + [d for d, _ in pairs]
    requests = [(i, 0) for i in range(len(unshared))]
    for j, (_, q) in enumerate(pairs):
        requests += [(len(unshared) + j, q)] * 2
    rng = np.random.default_rng([seed, 1])
    prompts = _request_prompts(rng, heads, requests, range(len(requests)), vocab)
    return [Item(0.0, f"warm{i}", p, 2) for i, p in enumerate(prompts)]
