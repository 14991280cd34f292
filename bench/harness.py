"""One run of one cell: set-up, window, checks, and the result line.

A system module (``bench/systems/<name>.py``) exposes
``run(cell, seed, window, rec, dev) -> Outcome``.  It sets the system up,
calls ``window.open()`` right before the first timed unit of work, calls
``window.tick()`` after each unit and stops once it returns False, calls
``window.close()``, reads the device's memory peak, frees the system's
state and then checks what the window produced.  The harness turns that
into the result line; it never needs to know which system ran.
"""
from __future__ import annotations

import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

from bench import registry
from bench.spans import SPAN_PREFIX, Recorder

TRACE_DIR = registry.BENCH_DIR / ".trace"
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class CompileCounter:
    """Counts programs lowered in this process (compiled or cache hits)."""

    def __init__(self) -> None:
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, _secs: float, **_kw) -> None:
        if name == COMPILE_EVENT:
            self.n += 1


@dataclass
class Window:
    """The measured window, and the profiler around it when tracing.

    ``trace_seconds`` (from the traffic file) traces only the window's
    first seconds, for a cell whose whole window would give a trace too
    large to keep; the traced window is then that part.
    """

    seconds: float
    t_process: float
    traced: bool
    compiles: CompileCounter
    trace_seconds: Optional[float] = None
    t_open: Optional[float] = None
    t_close: Optional[float] = None
    t_trace_stop: Optional[float] = None
    compiles_before: int = 0
    compiles_inside: int = 0
    _annotation: Any = field(default=None, repr=False)
    _tracing: bool = False

    @property
    def setup_s(self) -> float:
        return self.t_open - self.t_process

    @property
    def elapsed(self) -> float:
        end = self.t_close if self.t_close is not None else time.monotonic()
        return end - self.t_open

    def open(self) -> None:
        if self.traced:
            import jax

            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            self._annotation = jax.profiler.TraceAnnotation(
                SPAN_PREFIX + "window")
            self._annotation.__enter__()
            self._tracing = True
        self.compiles_before = self.compiles.n
        self.t_open = time.monotonic()

    def tick(self) -> bool:
        """True while the window should go on."""
        now = time.monotonic()
        if (self._tracing and self.trace_seconds is not None
                and now - self.t_open >= self.trace_seconds):
            self._stop_trace()
        return now - self.t_open < self.seconds

    def close(self) -> None:
        self.t_close = time.monotonic()
        self.compiles_inside = self.compiles.n - self.compiles_before
        if self._tracing:
            self._stop_trace()

    def _stop_trace(self) -> None:
        import jax

        self.t_trace_stop = time.monotonic()
        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._tracing = False


@dataclass
class Outcome:
    """What a system module hands back after its window and its checks."""

    e2e: Dict[str, float]              # end-to-end values it measured
    ctx: Dict[str, Any]                # what per-layer readers may read
    checks: Dict[str, Dict[str, Any]]  # name -> {value, limit, ok}
    attempted: int
    failed: int
    memory_peak_bytes: int


def run_cell(cell: registry.Cell, *, seed: int, seconds: float,
             trace: bool, t_process: float, dev) -> tuple:
    compiles = CompileCounter()
    rec = Recorder(traced=trace)
    window = Window(seconds=seconds, t_process=t_process, traced=trace,
                    compiles=compiles,
                    trace_seconds=cell.traffic.get("trace_seconds"))
    system = registry.system(cell.config["system"])
    out: Outcome = system.run(cell, seed=seed, window=window, rec=rec,
                              dev=dev)
    print(f"bench: programs lowered inside the window: "
          f"{window.compiles_inside}", flush=True)

    device = {"platform": dev.platform, "kind": dev.kind, "count": dev.count,
              "memory_peak_bytes": out.memory_peak_bytes}
    result: Dict[str, Any] = {
        "correct": all(c["ok"] for c in out.checks.values()),
        "attempted": out.attempted, "failed": out.failed}
    metrics: Dict[str, Dict[str, Any]] = {}
    if not trace:
        values = dict(out.e2e, setup_s=window.setup_s)
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
            else:
                print(f"bench: {cell.name} measured no {m['name']}",
                      file=sys.stderr)
        result["metrics"] = metrics
        result["device"] = device
    else:
        from bench.trace import find_xplane, reduce_trace

        summary = reduce_trace(find_xplane(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = dict(out.ctx, trace=summary, peaks=dev.peaks,
                   window_s=window.elapsed,
                   measured_window=(window.t_open, window.t_close),
                   traced_window=(window.t_open, window.t_trace_stop))
        for m in cell.per_layer:
            value = registry.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = dict(device, busy_s=summary.busy_s,
                                window_s=summary.window_s)
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.top_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_by_span]}
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in out.checks.items()}
    return result, out.checks
