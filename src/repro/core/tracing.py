"""In-program spans on the profiler's clock, owned by a `Runtime`.

A `Tracer` is off until `enable()`. Off, `span()` hands back one shared
no-op context manager after a single attribute check: no clock read, no
record, no new object. On, each span records ``(id, parent_id, name, t0_ns,
t1_ns, attrs)`` with `time.perf_counter_ns` (the clock a harness times its
own calls with) into a bounded ring, and also enters
`jax.profiler.TraceAnnotation(name)`, so a running profiler shows the same
span on its host plane, on the timeline of the device ops.

Parents come from a per-thread stack: threads that share a tracer each keep
their own tree. While the tracer is on, every XLA compile that happens
inside an open span is recorded as that span's child ``xla.compile``.
Records stay in memory until the caller `drain()`s them; there is no
exporter.
"""
from __future__ import annotations

import collections
import itertools
import threading
import weakref
from time import perf_counter_ns
from typing import Dict, List, NamedTuple, Optional

#: the JAX monitoring event a backend compile reports its duration under
_COMPILE_EVENT = "backend_compile"


class Span(NamedTuple):
    """One closed span. Times are `perf_counter_ns()` readings."""

    id: int
    parent_id: Optional[int]
    name: str
    t0_ns: int
    t1_ns: int
    attrs: Dict[str, object]


class Drained(NamedTuple):
    """What `Tracer.drain()` hands over: the records in the order they
    closed (children before their parent), and how many the full ring
    dropped since the last drain."""

    spans: List[Span]
    dropped: int


class _NoopSpan:
    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        """Counts recorded at the span's close; nothing when tracing is off."""


NOOP = _NoopSpan()


class _LiveSpan:
    __slots__ = ("tracer", "name", "attrs", "id", "parent_id", "t0", "annotation")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_LiveSpan":
        tr = self.tracer
        stack = tr._stack()
        self.parent_id = stack[-1].id if stack else None
        self.id = next(tr._ids)
        stack.append(self)
        self.annotation = tr._annotation(self.name)
        self.annotation.__enter__()
        self.t0 = perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = perf_counter_ns()
        self.annotation.__exit__(*exc)
        tr = self.tracer
        tr._stack().pop()
        tr._record(Span(self.id, self.parent_id, self.name, self.t0, t1, self.attrs))
        return False

    def set(self, **attrs) -> None:
        """Counts recorded at the span's close, beside those given at entry."""
        self.attrs.update(attrs)


class Tracer:
    """Spans of one Runtime; off by default."""

    def __init__(self):
        self.enabled = False
        self.dropped = 0
        self._ring: collections.deque = collections.deque()
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._lock = threading.Lock()
        self._annotation = None

    def enable(self, capacity: int = 65536) -> None:
        """Start recording into a ring of `capacity` spans (records already
        held are kept, up to the new capacity)."""
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        with self._lock:
            if self._ring.maxlen != capacity:
                self._ring = collections.deque(self._ring, maxlen=capacity)
        _listen_compiles(self)
        self.enabled = True

    def disable(self) -> None:
        """Stop recording; spans open now still record when they close."""
        self.enabled = False

    def drain(self) -> Drained:
        """Hand over and clear the records and the dropped count."""
        with self._lock:
            out = Drained(list(self._ring), self.dropped)
            self._ring.clear()
            self.dropped = 0
        return out

    def span(self, name: str, **attrs):
        """A context manager timing its block as `name`. `attrs` and later
        `set()` calls become the record's counts."""
        if not self.enabled:
            return NOOP
        return _LiveSpan(self, name, attrs)

    # -- internals -------------------------------------------------------------
    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _record(self, span: Span) -> None:
        with self._lock:
            ring = self._ring
            if len(ring) == ring.maxlen:
                self.dropped += 1
            ring.append(span)

    def _on_compile(self, seconds: float) -> None:
        """Record an XLA compile that just ended in this thread as a child
        of the innermost span this thread has open."""
        stack = getattr(self._local, "stack", None)
        if not self.enabled or not stack:
            return
        t1 = perf_counter_ns()
        self._record(Span(next(self._ids), stack[-1].id, "xla.compile",
                          t1 - int(seconds * 1e9), t1, {}))


#: tracers that have been enabled in this process; the one JAX listener
#: (JAX offers no per-object registration) hands each compile to them
_tracers: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_listening = False
_listen_lock = threading.Lock()


def _listen_compiles(tracer: Tracer) -> None:
    global _listening
    with _listen_lock:
        _tracers.add(tracer)
        if _listening:
            return
        import jax.monitoring

        def listener(event: str, duration: float, **_) -> None:
            if _COMPILE_EVENT in event:
                for tr in list(_tracers):
                    tr._on_compile(duration)

        jax.monitoring.register_event_duration_secs_listener(listener)
        _listening = True
