#!/usr/bin/env python3
"""What the program's spans cost, and what they see in a benchmark cell.

    python3 tools/trace_cost.py span-cost [--batches 15] [--spans 100000]
    python3 tools/trace_cost.py cell --workload <name> --seed <n> --seconds <s>

``span-cost`` times an empty ``repro.tracing`` span in batches, with no
profiler session running and inside one, and prints one JSON line: the
microseconds a span costs in each batch, beside an empty loop's.

``cell`` runs one benchmark cell with the profiler on, as
``bench/run.py --trace 1`` does, and prints one JSON line with

* ``metrics``: the cell's per-layer metrics, read as the benchmark reads
  them;
* ``traced_e2e``: its end-to-end metrics over the traced part of the
  window (``bench/run.py`` prints none on a traced run), to hold against
  untraced runs;
* ``spans``: the count and seconds of each program span over the traced
  part of the window, and ``counters``: the program's counters at the end;
* ``idle_gaps``: the device's idle seconds under the innermost host span,
  as the benchmark reads them, and ``idle_gaps_program``: the same with
  the program's ``repro.*`` spans laid beside the benchmark's.

Both need the chips the cell asks for; on a host with none they exit 1.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

T_PROCESS = time.monotonic()
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # as bench/run.py

from repro import tracing  # noqa: E402


def _per_span(n: int) -> float:
    t = tracing.Tracer(capacity=1024)
    t0 = time.perf_counter()
    for _ in range(n):
        with t.span("cost.probe", id=1):
            pass
    return (time.perf_counter() - t0) / n


def _per_pass(n: int) -> float:
    t0 = time.perf_counter()
    for _ in range(n):
        pass
    return (time.perf_counter() - t0) / n


def span_cost(batches: int, n: int) -> dict:
    import jax

    jax.devices()       # the profiler session below then sees the device
    off = [_per_span(n) for _ in range(batches)]
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            on = [_per_span(n) for _ in range(batches)]
        finally:
            jax.profiler.stop_trace()
    empty = [_per_pass(n) for _ in range(batches)]
    us = lambda xs: [1e6 * x for x in xs]
    return {"spans_per_batch": n, "platform": jax.devices()[0].device_kind,
            "us_per_span_off": us(off), "us_per_span_on": us(on),
            "us_per_empty_pass": us(empty),
            "median_us_off": 1e6 * statistics.median(off),
            "median_us_on": 1e6 * statistics.median(on)}


def program_host_spans(path: str) -> list:
    """The program's spans on the trace's host plane, from every thread."""
    from jax.profiler import ProfileData

    found = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(tracing.PREFIX):
                    found.append((e.name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9))
    return found


def traced_e2e(out, window) -> dict:
    """End-to-end values over the traced part of the window.  A cell that
    traces only its first seconds (the engine's sweeps) is read from its
    sweeps that ended before the profiler stopped."""
    stop = window.t_trace_stop
    if window.trace_seconds is None or stop is None or stop >= window.t_close:
        return dict(out.e2e)
    inside = [(b, n) for _, b, n in out.ctx.get("sweeps_log", []) if b <= stop]
    if not inside:
        return {}
    return {"engine_cells_per_s":
            sum(n for _, n in inside) / (inside[-1][0] - window.t_open)}


def cell(workload: str, seed: int, seconds: float) -> dict:
    from bench import device, harness, registry, trace
    from bench.spans import Recorder
    from repro.launch.compile_cache import enable_compile_cache

    c = registry.resolve(workload)
    dev = device.require_chips(c.chips)
    enable_compile_cache()
    window = harness.Window(seconds=seconds, t_process=T_PROCESS, traced=True,
                            compiles=harness.CompileCounter(),
                            trace_seconds=c.traffic.get("trace_seconds"))
    out = registry.system(c.config["system"]).run(
        c, seed=seed, window=window, rec=Recorder(traced=True), dev=dev)
    path = trace.find_xplane(str(harness.TRACE_DIR))
    host, modules, ops = trace.load_events(path)
    summary = trace.summarize(host, modules, ops, top=20)
    with_program = trace.summarize(host + program_host_spans(path), modules,
                                   ops, top=20)
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)

    t_stop = window.t_trace_stop or window.t_close
    ctx = dict(out.ctx, trace=summary, peaks=dev.peaks,
               window_s=window.elapsed,
               measured_window=(window.t_open, window.t_close),
               traced_window=(window.t_open, window.t_trace_stop))
    metrics = {}
    for m in c.per_layer:
        value = registry.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = value
    spans = {}
    for s in tracing.spans(window.t_open) or []:
        if s.t0 < t_stop:
            n, secs = spans.get(s.name, (0, 0.0))
            spans[s.name] = (n + 1, secs + s.seconds)
    return {
        "workload": workload, "seed": seed,
        "correct": all(ch["ok"] for ch in out.checks.values()),
        "window_s": window.elapsed, "traced_s": t_stop - window.t_open,
        "busy_s": summary.busy_s, "trace_window_s": summary.window_s,
        "metrics": metrics, "traced_e2e": traced_e2e(out, window),
        "spans": {k: {"n": n, "s": secs}
                  for k, (n, secs) in sorted(spans.items())},
        "counters": tracing.counters(),
        "idle_gaps": summary.idle_by_span,
        "idle_gaps_program": with_program.idle_by_span}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    cost = sub.add_parser("span-cost")
    cost.add_argument("--batches", type=int, default=15)
    cost.add_argument("--spans", type=int, default=100_000)
    run = sub.add_parser("cell")
    run.add_argument("--workload", required=True)
    run.add_argument("--seed", type=int, required=True)
    run.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import device

    try:
        if args.what == "span-cost":
            device.require_chips(1)
            result = span_cost(args.batches, args.spans)
        else:
            result = cell(args.workload, args.seed, args.seconds)
    except device.NoChip as e:
        print(f"trace_cost: {e}; nothing was run", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
