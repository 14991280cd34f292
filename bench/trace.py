"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

A TPU plane is named ``/device:TPU:<n>``.  Its ``XLA Modules`` line holds
one event per program run on the device; those events do not nest, so the
union of their intervals is the time the device was busy.  Its ``XLA Ops``
line holds the operations inside them (nested: a loop and its body both
appear), which rank operations by time and give the device time of
chosen ones, such as the collectives.  The host plane ``/host:CPU`` holds,
on the same clock and on one line per thread, the benchmark's own spans,
named ``bench.<name>`` (``spans.Recorder``), and the program's, named
``repro.<name>`` (``repro.tracing``).

``reduce_trace`` gives, for the traced window (the ``bench.window`` span):
busy seconds per device and their mean, the device time of each program,
the operations that took most time, and the idle time on the device
grouped by the innermost benchmark or program span that was open on the
host then.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from bench.spans import SPAN_PREFIX

WINDOW_SPAN = SPAN_PREFIX + "window"
PROGRAM_SPAN_PREFIX = "repro."
HOST_SPAN_PREFIXES = (SPAN_PREFIX, PROGRAM_SPAN_PREFIX)
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
NO_SPAN = "no benchmark span"
_MODULE_ID = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


@dataclass
class TraceSummary:
    window_s: float
    busy_s_per_device: Dict[int, float]
    module_s: Dict[str, float]           # program name -> device seconds
    module_runs: Dict[str, int]
    top_ops: List[Tuple[str, float]]     # by total device seconds
    idle_by_span: List[Tuple[str, float]]
    op_s: Dict[str, float]               # operation -> device seconds, all

    @property
    def n_devices(self) -> int:
        return len(self.busy_s_per_device)

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices in the trace."""
        if not self.busy_s_per_device:
            return 0.0
        return sum(self.busy_s_per_device.values()) / self.n_devices

    @property
    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0 or not self.busy_s_per_device:
            return None
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, pattern: str) -> Tuple[float, int]:
        """Device seconds and runs of programs whose name matches."""
        rx = re.compile(pattern)
        secs = sum(s for n, s in self.module_s.items() if rx.search(n))
        runs = sum(r for n, r in self.module_runs.items() if rx.search(n))
        return secs, runs

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of operations whose name matches, averaged over
        the devices in the trace.  Operations are read over the whole
        trace, which the profiler takes around the traced window only."""
        rx = re.compile(pattern)
        secs = sum(s for n, s in self.op_s.items() if rx.search(n))
        return secs / max(self.n_devices, 1)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals; the result is sorted and disjoint."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi) that the disjoint sorted ``busy`` leaves."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def attribute(gap: Interval, spans: Sequence[Tuple[str, float, float]]
              ) -> Dict[str, float]:
    """Split a gap among the innermost host spans open during each part."""
    a, b = gap
    inside = [s for s in spans if s[1] < b and s[2] > a]
    cuts = sorted({a, b, *(t for _, s0, s1 in inside for t in (s0, s1)
                           if a < t < b)})
    out: Dict[str, float] = {}
    for lo, hi in zip(cuts, cuts[1:]):
        mid = 0.5 * (lo + hi)
        best, best_len = NO_SPAN, float("inf")
        for name, s0, s1 in inside:
            if s0 <= mid < s1 and s1 - s0 < best_len:
                best, best_len = name, s1 - s0
        out[best] = out.get(best, 0.0) + (hi - lo)
    return out


def _module_name(name: str) -> str:
    """``jit_train_step(2652326001846272656)`` -> ``jit_train_step``."""
    return _MODULE_ID.sub("", name)


def _op_name(name: str) -> str:
    """``%fusion.12 = (bf16[...]) fusion(...)`` -> ``fusion.12``."""
    head = name.split(" = ", 1)[0]
    return head.lstrip("%")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def reduce_trace(path: str, top: int = 10) -> TraceSummary:
    return summarize(*load_events(path), top=top)


def load_events(path: str):
    """Benchmark and program host spans, each device's program runs, and
    the device seconds of each operation, from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host_spans: List[Tuple[str, float, float]] = []
    device_modules: Dict[int, List[Tuple[str, float, float]]] = {}
    op_time: Dict[str, float] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIXES):
                        host_spans.append((e.name, e.start_ns * 1e-9,
                                           (e.start_ns + e.duration_ns) * 1e-9))
        elif m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Modules":
                    device_modules[dev] = [
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
                elif line.name == "XLA Ops":
                    for e in line.events:
                        k = _op_name(e.name)
                        op_time[k] = op_time.get(k, 0.0) + e.duration_ns * 1e-9
    return host_spans, device_modules, op_time


def summarize(host_spans: Sequence[Tuple[str, float, float]],
              device_modules: Dict[int, List[Tuple[str, float, float]]],
              op_time: Dict[str, float], top: int = 10) -> TraceSummary:
    windows = [(a, b) for n, a, b in host_spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0]
    else:  # no window span: the whole extent of the device events
        ends = [(a, b) for evs in device_modules.values() for _, a, b in evs]
        lo = min((a for a, _ in ends), default=0.0)
        hi = max((b for _, b in ends), default=0.0)
    inner = [s for s in host_spans if s[0] != WINDOW_SPAN]

    busy_per_dev: Dict[int, float] = {}
    module_s: Dict[str, float] = {}
    module_runs: Dict[str, int] = {}
    idle: Dict[str, float] = {}
    for dev, evs in sorted(device_modules.items()):
        busy = union(clip([(a, b) for _, a, b in evs], lo, hi))
        busy_per_dev[dev] = sum(b - a for a, b in busy)
        for name, a, b in evs:
            part = min(b, hi) - max(a, lo)
            if part > 0:
                k = _module_name(name)
                module_s[k] = module_s.get(k, 0.0) + part
                module_runs[k] = module_runs.get(k, 0) + 1
        for gap in gaps(busy, lo, hi):
            for label, secs in attribute(gap, inner).items():
                idle[label] = idle.get(label, 0.0) + secs
    n_dev = max(len(busy_per_dev), 1)
    idle_mean = sorted(((k.removeprefix(SPAN_PREFIX), v / n_dev)
                        for k, v in idle.items()), key=lambda kv: -kv[1])
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return TraceSummary(
        window_s=hi - lo, busy_s_per_device=busy_per_dev,
        module_s=module_s, module_runs=module_runs,
        top_ops=[(k, v / n_dev) for k, v in top_ops],
        idle_by_span=idle_mean[:top], op_s=dict(op_time))
