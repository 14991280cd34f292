"""The trace reduction, on interval arithmetic and on a trace recorded on a
TPU v5e: three steps of the two-layer OLMo cut, each under a
``bench.step`` span, with the profiler's Python tracer on."""
from pathlib import Path

import pytest

from bench import trace

FIXTURE = Path(__file__).parent / "data" / "olmo-1b-2l.steps.xplane.pb"


def test_union_gaps_and_clip():
    busy = trace.union([(3, 4), (0, 1), (0.5, 2), (3.5, 3.7)])
    assert busy == [(0, 2), (3, 4)]
    assert trace.gaps(busy, -1, 5) == [(-1, 0), (2, 3), (4, 5)]
    assert trace.clip(busy, 1, 3.5) == [(1, 2), (3, 3.5)]


@pytest.fixture(scope="module")
def summary():
    return trace.reduce_trace(str(FIXTURE))


def test_device_busy_is_the_union_of_program_runs(summary):
    # One device; three runs of the step program of 142.19, 142.17 and
    # 142.17 ms (read from the trace's XLA Modules line by hand).
    assert summary.n_devices == 1
    secs, runs = summary.module_seconds(r"train_step")
    assert runs == 3
    assert secs == pytest.approx(0.426530292, rel=1e-6)
    assert summary.busy_s == pytest.approx(secs, rel=1e-9)
    # No window span in this trace: the window is the device's extent,
    # from the first program's start to the last one's end.
    assert summary.window_s == pytest.approx(0.434628514, rel=1e-6)
    assert 0.0 < summary.idle_share < 0.05


def test_idle_time_is_attributed_to_host_spans(summary):
    labels = dict(summary.idle_by_span)
    assert set(labels) <= {"step", trace.NO_SPAN}
    assert sum(labels.values()) == pytest.approx(
        summary.window_s - summary.busy_s, rel=1e-9)


def test_top_ops_are_ranked_by_device_time(summary):
    secs = [s for _, s in summary.top_ops]
    assert len(secs) == 10 and secs == sorted(secs, reverse=True)
    assert all(" " not in name for name, _ in summary.top_ops)


def test_window_span_bounds_the_reduction():
    # Two devices; the window cuts the second program on device 0 in half.
    spans = [("bench.window", 0.0, 4.0), ("bench.sweep", 0.0, 4.0),
             ("bench.engine.pack", 1.0, 2.0)]
    modules = {0: [("jit_chunk(1)", 0.0, 1.0), ("jit_chunk(1)", 3.0, 5.0)],
               1: [("jit_chunk(2)", 2.0, 4.0)]}
    s = trace.summarize(spans, modules, {"fusion.1": 3.0})
    assert s.window_s == 4.0
    assert s.busy_s_per_device == {0: 2.0, 1: 2.0}
    assert s.idle_share == pytest.approx(0.5)
    assert s.module_seconds("chunk") == (4.0, 3)
    # Idle: device 0 in [1, 3): pack in [1, 2), then sweep; device 1 in
    # [0, 2): sweep, then pack.  Averaged over the two devices.
    assert dict(s.idle_by_span) == {"engine.pack": 1.0, "sweep": 1.0}
    assert trace.attribute((0.5, 3.0), spans[1:]) == {
        "bench.sweep": 1.5, "bench.engine.pack": 1.0}
    assert trace.attribute((5.0, 6.0), spans) == {trace.NO_SPAN: 1.0}


def test_program_spans_leave_every_reading_unchanged():
    # The reduction reads the program's host spans too; what the recorded
    # trace gives every metric stays as it was with benchmark spans alone.
    from bench import registry
    from bench.spans import SPAN_PREFIX

    spans, modules, ops = trace.load_events(str(FIXTURE))
    both = trace.summarize(spans, modules, ops)
    bench_only = trace.summarize(
        [s for s in spans if s[0].startswith(SPAN_PREFIX)], modules, ops)
    for field in ("window_s", "busy_s_per_device", "module_s", "module_runs",
                  "top_ops", "op_s"):
        assert getattr(both, field) == getattr(bench_only, field), field
    for name in ("device_idle_share.train", "train_step_device_ms"):
        read = registry.metric_reader(name).read
        assert read({"trace": both}) == read({"trace": bench_only}), name


def test_load_events_keeps_program_spans_of_every_thread(tmp_path):
    # A trace recorded here on the CPU: a benchmark span on the main
    # thread, the program's spans on it and on a second thread.
    import threading

    import jax

    from bench.spans import Recorder
    from repro import tracing

    def writer():
        with tracing.span("ckpt.write", id=5):
            with tracing.span("ckpt.hash"):
                pass

    jax.profiler.start_trace(str(tmp_path))
    try:
        with Recorder(traced=True).span("window"):
            with tracing.span("sim.run_cells", id=1):
                pass
            t = threading.Thread(target=writer)
            t.start()
            t.join(timeout=60)
    finally:
        jax.profiler.stop_trace()
    assert not t.is_alive()
    spans, _, _ = trace.load_events(trace.find_xplane(str(tmp_path)))
    assert {n for n, _, _ in spans} == {
        "bench.window", "repro.sim.run_cells", "repro.ckpt.write",
        "repro.ckpt.hash"}
    assert all(a <= b for _, a, b in spans)


def test_op_seconds_is_a_mean_over_devices():
    modules = {0: [("jit_engine_chunk(1)", 0.0, 1.0)],
               1: [("jit_engine_chunk(1)", 0.0, 1.0)]}
    ops = {"all-reduce.3": 0.002, "all-reduce-start.1": 0.001,
           "while.8": 1.9, "fusion.1": 0.05}
    s = trace.summarize([], modules, ops)
    assert s.op_seconds(r"^all-reduce") == pytest.approx(0.0015)
    assert s.op_seconds(r"^all-gather") == 0.0


def test_collective_reader_counts_sweeps_of_the_traced_window():
    from bench import registry

    read = registry.metric_reader("engine_collective_us_per_sweep").read
    modules = {d: [("jit_engine_chunk(1)", 0.0, 4.0)] for d in range(4)}
    # 4 devices x 6 chunks x 2 sweeps of a 20 us all-reduce each.
    s = trace.summarize([], modules, {"all-reduce.3": 4 * 12 * 20e-6,
                                      "while.8": 15.0})
    ctx = {"trace": s, "traced_window": (10.0, 15.0),
           "sweeps_log": [(10.0, 12.0, 1024), (12.0, 14.0, 1024),
                          (14.0, 16.0, 1024)]}
    assert read(ctx) == pytest.approx(6 * 20.0)
    # One chip: no collective in the trace, nothing to read.
    s1 = trace.summarize([], {0: modules[0]}, {"while.8": 4.0})
    assert read(dict(ctx, trace=s1)) is None
