"""Spans and counters of the program's host work, always recorded.

A span is one named interval of host work, timed on ``time.monotonic``:

    with tracing.span("ckpt.save", id=step) as sp:
        ...
    blocking = sp.seconds

Each span keeps its name, its ``id`` (spans of one request share it: the
step of a save and of its write phases), its ``parent`` (the span that
caused it), its thread, and its start and end.  The parent is the
innermost span open on the same thread unless one is passed, which is how
work handed to another thread names its cause; a span with no ``id`` takes
its parent's.  A span is closed and kept even when its body raises.

Closed spans go to a bounded in-memory ring (``RING_SPANS``), oldest
dropped first; ``spans(t_from, t_to)`` reads it back and gives ``None``
once the ring has dropped spans that may belong to that interval, so a
reader never sums a partial window.  ``count(name, n)`` keeps a running
total and returns it (``counters()`` reads them all); a counter has no
time, so a reader divides one total by another.

Each span also enters ``jax.profiler.TraceAnnotation("repro." + name)``.
While a profiler session runs, the span then appears on the trace's host
plane, on the same clock as the device's programs, so idle time on the
device can be laid against the host work that was open at the time.  With
no session running the annotation is a cheap no-op.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, Optional

try:
    from jax.profiler import TraceAnnotation
except ImportError:  # pragma: no cover - the numpy-only engine path
    TraceAnnotation = None

PREFIX = "repro."
RING_SPANS = 16_384


class Span:
    """One span: open inside its ``with`` block, recorded when it closes."""

    __slots__ = ("name", "id", "parent", "thread", "t0", "t1", "seq",
                 "_tracer", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, id, parent: Optional["Span"]):
        self._tracer = tracer
        self.name = name
        self.id = parent.id if id is None and parent is not None else id
        self.parent = None if parent is None else parent.seq
        self.seq = next(tracer._seq)
        self.thread = threading.get_ident()
        self.t0 = self.t1 = None
        self._annotation = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def __enter__(self) -> "Span":
        self._tracer._stack().append(self)
        if TraceAnnotation is not None:
            self._annotation = TraceAnnotation(PREFIX + self.name)
            self._annotation.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.monotonic()
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        self._tracer._stack().pop()
        self._tracer._record(self)

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, id={self.id!r}, seq={self.seq}, "
                f"parent={self.parent}, t0={self.t0}, t1={self.t1})")


class Tracer:
    """A ring of closed spans, the open spans of each thread, and counters."""

    def __init__(self, capacity: int = RING_SPANS):
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._dropped_until: Optional[float] = None  # latest dropped end
        self._counts: Dict[str, int] = {}
        self._seq = itertools.count(1)
        self._local = threading.local()

    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, *, id=None, parent: Optional[Span] = None
             ) -> Span:
        """A span to open with ``with``; see the module docstring."""
        if parent is None:
            stack = self._stack()
            parent = stack[-1] if stack else None
        return Span(self, name, id, parent)

    def _record(self, sp: Span) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                end = self._ring[0].t1
                if self._dropped_until is None or end > self._dropped_until:
                    self._dropped_until = end
            self._ring.append(sp)

    def count(self, name: str, n: int = 1) -> int:
        """Add ``n`` to the counter ``name`` and return its new total."""
        with self._lock:
            total = self._counts[name] = self._counts.get(name, 0) + n
        return total

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def spans(self, t_from: float = float("-inf"),
              t_to: float = float("inf")) -> Optional[List[Span]]:
        """Closed spans that started in [t_from, t_to), in the order they
        closed; None when the ring may have dropped some of them."""
        with self._lock:
            if (self._dropped_until is not None
                    and self._dropped_until >= t_from):
                return None
            held = list(self._ring)
        return [s for s in held if t_from <= s.t0 < t_to]


_TRACER = Tracer()
span = _TRACER.span
count = _TRACER.count
counters = _TRACER.counters
spans = _TRACER.spans
