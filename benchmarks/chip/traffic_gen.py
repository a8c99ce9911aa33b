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
  is constant.

Arrivals are open loop and Poisson at the cell's rate, shaped by the
profile: unit-rate exponential gaps are mapped through the inverse of the
profile's cumulative rate (time rescaling), so a burst packs the same gaps
closer. Every seed gets the same multiset of gaps, prompt lengths and
output lengths, in an order drawn uniformly from the seed: each position
then has the distribution's own marginal, and runs of short gaps come as
they do in a Poisson stream, while the amount of work does not change from
seed to seed.

Every request opens with a first token no other prompt of the run opens
with, so the radix cache shares nothing by accident: a one-token partial
match would make a tail length outside the table.
"""
from __future__ import annotations

import bisect
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


def rescale(s: np.ndarray, rate: float, profile) -> np.ndarray:
    """Times at which a Poisson stream of mean rate `rate`, shaped by the
    cycled `profile` ([[seconds, relative rate], ...], normalised to a mean
    of 1), reaches the unit-rate arrival times `s`."""
    durs = np.array([p[0] for p in profile], float)
    rel = np.array([p[1] for p in profile], float)
    rel = rel * durs.sum() / (durs * rel).sum()
    period = durs.sum()
    # cumulative expected arrivals at each phase boundary of one period
    bounds = np.concatenate([[0.0], np.cumsum(durs)])
    lam = np.concatenate([[0.0], np.cumsum(durs * rel * rate)])
    per_period = lam[-1]
    out = np.empty_like(s)
    for i, v in enumerate(s):
        k, r = divmod(v, per_period)
        j = min(bisect.bisect_right(lam, r) - 1, len(durs) - 1)
        out[i] = k * period + bounds[j] + (r - lam[j]) / (rel[j] * rate)
    return out


def tail_lengths(traffic: dict) -> List[int]:
    """Every prefill tail length the mix can produce: with nothing shared,
    the whole prompt."""
    return sorted(set(traffic["prompt_lengths"]))


def _prompts(rng, lengths, vocab: int) -> List[List[int]]:
    firsts = rng.choice(np.arange(1, vocab), size=len(lengths), replace=False)
    return [[int(f)] + rng.integers(1, vocab, int(n) - 1).tolist()
            for f, n in zip(firsts, lengths)]


def schedule(traffic: dict, *, rate: float, span: float, pre: float, seed: int,
             vocab: int) -> List[Item]:
    """Requests due over ``span`` seconds, the first ``pre`` of them before
    the window opens (due < 0). ``rate`` is the mean in requests per second."""
    n = max(1, int(math.ceil(rate * span)))
    rng = np.random.default_rng(seed)
    gaps = rng.permutation([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    dues = rescale(np.cumsum(gaps) - gaps[0], rate,
                   traffic.get("rate_profile", [[1.0, 1.0]])) - pre
    table = traffic["prompt_lengths"]
    plens = rng.permutation(np.asarray(table)[np.arange(n) % len(table)])
    outs = rng.permutation(quantiles(traffic["output"], n))
    prompts = _prompts(rng, plens, vocab)
    return [Item(float(dues[i]), f"r{i}", prompts[i], int(outs[i])) for i in range(n)]


def warm_items(traffic: dict, vocab: int, seed: int) -> List[Item]:
    """Requests that, served one after another, run every prefill tail
    length the mix can produce through `try_admit`."""
    rng = np.random.default_rng([seed, 1])
    prompts = _prompts(rng, tail_lengths(traffic), vocab)
    return [Item(0.0, f"warm{i}", p, 2) for i, p in enumerate(prompts)]
