"""Per-request readings of the program's own spans and counters
(``repro.tracing``).

The program keeps its spans in an in-memory ring on the same clock as the
benchmark's window (``time.monotonic``).  A request is a span such as one
``ckpt.save`` or one ``sim.run_cells`` call; its parts are the spans that
share its ``id``, on any thread (a save's write phases run on the
checkpointer's writer thread).

The window read is the traced part of the measured window.  A cell may
trace only its first seconds (``trace_seconds``); stopping the profiler
then takes tens of seconds inside the window, and the requests after it
run on a host still busy with that (an engine sweep's upload read 270 ms
against 21 ms before, on a TPU v5e host), which no untraced run sees.

A counter is a running total with no time, so ``per_call`` divides one
total by another over the whole run, set-up included; in the cells that
read one, every request of the run has the same shape.

Both are None where there is nothing to read: a program without
``repro.tracing``, a ring that no longer holds the whole window, or no
request to divide by.
"""
from __future__ import annotations

from typing import Any, Dict, Optional


def _tracing():
    try:
        from repro import tracing
    except ImportError:
        return None
    return tracing


def per_request(ctx: Dict[str, Any], request: str, part: str
                ) -> Optional[float]:
    """Seconds of ``part`` spans per ``request`` span that started in the
    traced window, summed within each request and averaged over them."""
    tracing = _tracing()
    if tracing is None:
        return None
    t_open, t_close = ctx["measured_window"]
    traced = ctx.get("traced_window")
    if traced is not None and traced[1] is not None:
        t_close = min(t_close, traced[1])
    # A request's parts may end after the window closes; they are read too.
    spans = tracing.spans(t_open)
    if spans is None:
        return None
    ids = {s.id for s in spans if s.name == request and s.t0 < t_close}
    if not ids:
        return None
    return sum(s.seconds for s in spans
               if s.name == part and s.id in ids) / len(ids)


def per_call(counter: str, calls: str) -> Optional[float]:
    """The total of ``counter`` over the total of ``calls``."""
    tracing = _tracing()
    if tracing is None:
        return None
    totals = tracing.counters()
    if not totals.get(calls):
        return None
    return totals.get(counter, 0) / totals[calls]
