"""Reduction of a profiler trace to device busy time, op times and idle gaps.

`extract(path)` reads the ``.xplane.pb`` the JAX profiler wrote (found by
`find_xplane`) with `jax.profiler.ProfileData` and hands plain tuples to
`reduce`, which does the arithmetic and is what the tests check
(`program_spans.summarize` joins them, naming the gaps by program span):

* the window is the span of the benchmark's host annotations (``try_admit``
  and ``step``), on the trace's own clock;
* busy time is the union of the intervals in which an XLA op ran on a
  device, clipped to the window, averaged over the devices;
* ops that only contain others (loops, branches, calls) are dropped, since
  their children are on the same line;
* op time is the sum of op durations, keyed by the op's HLO path
  (``tf_op``/``long_name`` where the trace has them, else the op's
  instruction name and result shape: a Pallas kernel's custom call carries
  the kernel's name);
* each idle gap is named by the host annotation it fell in (``driver`` when
  none: the benchmark's own loop, or waiting for an arrival).
"""
from __future__ import annotations

import glob
import re
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

HOST_SPANS = ("try_admit", "step")
Interval = Tuple[float, float]  # (start_ns, end_ns)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Sequence[Interval], w0: float, w1: float) -> List[Interval]:
    return [(max(a, w0), min(b, w1)) for a, b in intervals if b > w0 and a < w1]


#: ops that only contain others (a loop, a branch, a call): their children
#: are listed on the same trace line, so they count neither as busy time
#: of their own nor as op time
CONTAINER = re.compile(r"\s(while|conditional|call)\(")
LAYOUT = re.compile(r"\{[^{}]*\}")


def op_key(name: str, stats: dict) -> str:
    """A short key for an op: its HLO path where the trace has one, else its
    instruction name and result shape, without layouts (``%fusion.3 =
    bf16[48,24576]``)."""
    for k in ("tf_op", "long_name"):
        v = stats.get(k)
        if isinstance(v, str) and v:
            return v
    if " = " not in name:
        return re.sub(r"\.\d+$", "", name)
    head, rest = name.split(" = ", 1)
    rest = LAYOUT.sub("", rest[:2000])
    shape = rest[: rest.find(")") + 1] if rest.startswith("(") else rest.split(" ", 1)[0]
    return f"{head} = {shape}"[:160]


def is_container(name: str) -> bool:
    return bool(CONTAINER.search(name.split(" = ", 1)[-1][:4000]))


def reduce(device_ops: Dict[str, List[Tuple[float, float, str]]],
           host_spans: List[Tuple[float, float, str]], top: int = 10) -> dict:
    """device_ops: device -> [(start_ns, end_ns, key)]; host_spans:
    [(start_ns, end_ns, name)]. Returns seconds."""
    if not host_spans or not device_ops:
        return {}
    w0 = min(a for a, _, _ in host_spans)
    w1 = max(b for _, b, _ in host_spans)
    busy_total = 0.0
    op_ns: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for dev, ops in sorted(device_ops.items()):
        merged = union(clip([(a, b) for a, b, _ in ops], w0, w1))
        busy_total += sum(b - a for a, b in merged)
        for a, b, key in ops:
            if b > w0 and a < w1:
                op_ns[key] = op_ns.get(key, 0.0) + (min(b, w1) - max(a, w0))
        if not gaps:  # idle gaps of the first device
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    n_dev = len(device_ops)
    spans = sorted(host_spans)

    def host_at(t: float) -> str:
        for a, b, name in spans:
            if a <= t < b:
                return name
        return "driver"

    named = sorted(((b - a, host_at((a + b) / 2)) for a, b in gaps), reverse=True)
    ops_sorted = sorted(op_ns.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total / n_dev * 1e-9,
        "op_s": {k: v * 1e-9 / n_dev for k, v in ops_sorted},
        "breakdown": {
            "device_ops": [[k, v * 1e-9 / n_dev] for k, v in ops_sorted[:top]],
            "idle_gaps": [[name, d * 1e-9] for d, name in named[:top]],
        },
    }


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "TPU" in plane_name and "Core" not in plane_name


def extract(path: str):
    """(device_ops, host_spans) from one .xplane.pb file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops: Dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if _is_device(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    if not is_container(e.name):
                        ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                    op_key(e.name, dict(e.stats))))
            if ops:
                device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    return device_ops, host


def find_xplane(trace_dir) -> str:
    files = sorted(glob.glob(str(Path(trace_dir) / "plugins" / "profile" / "*" / "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def describe(trace_dir, limit: int = 40) -> str:
    """A look at one trace by hand: planes, lines, and the first events of
    each device line with their stats."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(trace_dir))
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(evs)}")
            if _is_device(plane.name) or line.name == "python":
                for e in evs[:limit]:
                    out.append(f"    {e.name!r} start={e.start_ns} dur={e.duration_ns} "
                               f"stats={dict(e.stats)}")
    return "\n".join(out)
