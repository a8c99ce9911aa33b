"""The program's own spans, for a run that records them: idle gaps named by
program span, and the readers of the span metrics.

The serve path records spans on its Runtime's tracer (`repro.core.tracing`):
``serve.admit`` with its phases ``.match``, ``.reserve``, ``.prefill`` and
``.commit``; ``serve.step`` with ``.grow``, ``.interval`` and ``.harvest``;
``hicr.run:<unit>`` around each dispatch, with the blocking wait as its child
``hicr.wait:<unit>``; and ``xla.compile``. With the tracer on, each span is
also a `TraceAnnotation`, so it lies on the profiler's host plane beside the
benchmark's own ``try_admit`` and ``step``.

`label` cuts the benchmark's spans into disjoint pieces, each named by the
benchmark span it lies in joined to the innermost program span covering it
(``step/serve.step.harvest``), or by the benchmark span alone where no
program span covers it. Given to `trace_reduce.reduce` as its host spans,
the pieces leave the window, the busy time and the op times as they are,
and name each idle gap by the piece its midpoint falls in. With no program
spans the pieces are the benchmark's spans and the output is unchanged.

The readers take the records `Tracer.drain()` hands over (times in ns) for
the spans of one window, and return None when there is nothing to read.
"""
from __future__ import annotations

import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce

#: name prefixes of the program's spans on the profiler's host plane
PREFIXES = ("serve.", "hicr.", "xla.")
Named = Tuple[float, float, str]  # (start_ns, end_ns, name)


def label(host_spans: Sequence[Named], program_spans: Sequence[Named]) -> List[Named]:
    """Disjoint pieces of the benchmark's spans, named
    ``<benchmark span>/<innermost program span>`` or ``<benchmark span>``."""
    out: List[Named] = []
    for a, b, name in sorted(host_spans):
        inside = [(max(x, a), min(y, b), n) for x, y, n in program_spans if y > a and x < b]
        edges = sorted({a, b, *(x for x, _, _ in inside), *(y for _, y, _ in inside)})
        for x, y in zip(edges, edges[1:]):
            covering = [s for s in inside if s[0] <= x and s[1] >= y]
            # nested spans: the innermost starts last, and ends first
            inner = max(covering, key=lambda s: (s[0], -s[1]), default=None)
            piece = name if inner is None else f"{name}/{inner[2]}"
            if out and out[-1][2] == piece and out[-1][1] == x:
                out[-1] = (out[-1][0], y, piece)
            else:
                out.append((x, y, piece))
    return out


def extract_program(path: str) -> List[Named]:
    """The program's spans on the host planes of one .xplane.pb file."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    return spans


def summarize(trace_dir) -> dict:
    """`trace_reduce.summarize`, with idle gaps named by program span."""
    path = trace_reduce.find_xplane(trace_dir)
    device_ops, host = trace_reduce.extract(path)
    return trace_reduce.reduce(device_ops, label(host, extract_program(path)))


# -- readers -------------------------------------------------------------------


def _children(spans) -> Dict[Optional[int], list]:
    kids: Dict[Optional[int], list] = defaultdict(list)
    for s in spans:
        kids[s.parent_id].append(s)
    return kids


def _waited_ns(span, kids) -> int:
    """Time `span`'s descendants spent blocked on device work."""
    total = 0
    for k in kids.get(span.id, ()):
        if k.name.startswith("hicr.wait:"):
            total += k.t1_ns - k.t0_ns
        else:
            total += _waited_ns(k, kids)
    return total


def _seated(spans) -> list:
    return [s for s in spans if s.name == "serve.admit" and s.attrs.get("outcome") == "seated"]


def _median_ms(values_ns) -> Optional[float]:
    return statistics.median(values_ns) * 1e-6 if values_ns else None


def prefill_ms_p50(spans) -> Optional[float]:
    """Median `serve.admit.prefill` of the admissions that seated a request."""
    kids = _children(spans)
    return _median_ms([k.t1_ns - k.t0_ns for a in _seated(spans) for k in kids.get(a.id, ())
                       if k.name == "serve.admit.prefill"])


def admit_host_ms_p50(spans) -> Optional[float]:
    """Median over seating admissions of `serve.admit` less the device work
    it waited on (its ``hicr.wait:*`` descendants)."""
    kids = _children(spans)
    return _median_ms([a.t1_ns - a.t0_ns - _waited_ns(a, kids) for a in _seated(spans)])


def step_host_ms_p50(spans) -> Optional[float]:
    """Median over `serve.step` spans of the step less its wait for the
    decode interval: host time per interval with no decode queued."""
    kids = _children(spans)
    return _median_ms([s.t1_ns - s.t0_ns - _waited_ns(s, kids)
                       for s in spans if s.name == "serve.step"])


def slot_occupancy(spans) -> Optional[float]:
    """Percent of the slot-ticks the decode steps ran that gave a token."""
    steps = [s for s in spans if s.name == "serve.step"]
    slots = sum(s.attrs.get("slots", 0) for s in steps)
    if not slots:
        return None
    return 100.0 * sum(s.attrs.get("tokens", 0) for s in steps) / slots
