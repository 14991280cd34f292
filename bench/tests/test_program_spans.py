"""Per-layer metrics read from the program's own spans and counters
(``repro.tracing``): each reader on a window of spans or on counters made
here, its answer where there is
nothing to read, and the readings of a traced run through the harness on
the CPU.  Also: the trace reduction lays idle device time against the
innermost program span (``repro.<name>``) as it does for benchmark spans."""
import sys
import time

import pytest

from bench import registry, trace
from bench.tests.test_correct import run, tiny_engine_cell, tiny_trainer_cell
from repro import tracing

CKPT = ("ckpt_hash_s", "ckpt.hash"), ("ckpt_serialize_s", "ckpt.serialize"), \
    ("ckpt_fsync_s", "ckpt.fsync")
ENGINE = ("engine_upload_ms_per_sweep", "sim.upload"), \
    ("engine_download_ms_per_sweep", "sim.download")
# metric, counter, the counter of calls it is divided by, unit scale
COUNTED = (("ckpt_gb_per_save", "ckpt.bytes_written", "ckpt.saves", 1e-9),
           ("engine_host_syncs_per_sweep", "sim.host_syncs", "sim.run_cells",
            1.0))


def _reader(name):
    return registry.metric_reader(name).read


def _requests(request, parts, ids, pad=None):
    """Spans of a few requests, each with two of every part; the window
    runs from before the first to after the last."""
    t_open = time.monotonic()
    made = []
    for i in ids:
        with tracing.span(request, id=i):
            for part in parts * 2:
                with tracing.span(part) as sp:
                    time.sleep(0.001)
                made.append(sp)
    if pad is not None:   # a part of an unrelated request
        with tracing.span(parts[0], id=pad):
            pass
    return {"measured_window": (t_open, time.monotonic())}, made


@pytest.mark.parametrize("metric,part", CKPT + ENGINE,
                         ids=[m for m, _ in CKPT + ENGINE])
def test_reader_sums_its_part_per_request(metric, part):
    request = "ckpt.save" if part.startswith("ckpt") else "sim.run_cells"
    parts = [p for _, p in (CKPT if request == "ckpt.save" else ENGINE)]
    ctx, made = _requests(request, parts, ids=[10 ** 9 + 1, 10 ** 9 + 2],
                          pad=-1)
    want = sum(s.seconds for s in made if s.name == part) / 2
    scale = 1e3 if metric.endswith("_ms_per_sweep") else 1.0
    assert _reader(metric)(ctx) == pytest.approx(scale * want, rel=1e-12)


@pytest.mark.parametrize("metric,part", CKPT + ENGINE,
                         ids=[m for m, _ in CKPT + ENGINE])
def test_reader_keeps_to_the_traced_part_of_the_window(metric, part):
    request = "ckpt.save" if part.startswith("ckpt") else "sim.run_cells"
    ctx, first = _requests(request, [part], ids=[10 ** 9 + 3])
    t_stop = time.monotonic()
    later, _ = _requests(request, [part], ids=[10 ** 9 + 4])
    ctx = {"measured_window": (ctx["measured_window"][0],
                               later["measured_window"][1]),
           "traced_window": (ctx["measured_window"][0], t_stop)}
    scale = 1e3 if metric.endswith("_ms_per_sweep") else 1.0
    want = sum(s.seconds for s in first)
    assert _reader(metric)(ctx) == pytest.approx(scale * want, rel=1e-12)


@pytest.mark.parametrize("metric", [m for m, _ in CKPT + ENGINE])
def test_reader_gives_none_with_nothing_to_read(metric, monkeypatch):
    read = _reader(metric)
    now = time.monotonic()
    # No request started in the window.
    assert read({"measured_window": (now, now + 1.0)}) is None
    # The ring no longer holds the whole window.
    monkeypatch.setattr(tracing, "spans", lambda *a: None)
    ctx, _ = _requests("ckpt.save", ["ckpt.hash"], ids=[1])
    assert read(ctx) is None
    monkeypatch.undo()
    # A program without spans of its own.
    import repro

    monkeypatch.delattr(repro, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert read(ctx) is None


@pytest.mark.parametrize("metric,counter,calls,scale", COUNTED,
                         ids=[c[0] for c in COUNTED])
def test_counter_reader_divides_by_its_calls(metric, counter, calls, scale,
                                             monkeypatch):
    t = tracing.Tracer()
    monkeypatch.setattr(tracing, "counters", t.counters)
    read = _reader(metric)
    assert read({}) is None                  # nothing counted yet
    t.count(counter, 3_000_000_000)
    assert read({}) is None                  # no call to divide by
    for _ in range(3):
        t.count(calls)
    t.count(counter, 6_000_000_000)
    assert read({}) == pytest.approx(scale * 3e9, rel=1e-12)
    # A program without counters of its own.
    import repro

    monkeypatch.delattr(repro, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert read({}) is None


def test_traced_save_cell_reads_its_write_phases():
    r = run(tiny_trainer_cell(save_every_steps=5), trace=True)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    parts = [m[name] for name, _ in CKPT]
    assert all(p > 0 for p in parts), m
    # Disjoint phases inside the write, which also holds the commit's
    # renames and the bookkeeping between phases.
    assert sum(parts) <= m["ckpt_write_s"]
    assert m["ckpt_gb_per_save"] > 0


def test_traced_engine_cell_reads_its_transfers():
    r = run(tiny_engine_cell(), trace=True)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert m["engine_upload_ms_per_sweep"] > 0
    assert m["engine_download_ms_per_sweep"] > 0
    assert m["engine_host_syncs_per_sweep"] >= 1


def test_idle_gap_goes_to_the_innermost_program_span():
    spans = [("bench.window", 0.0, 10.0), ("bench.wait", 1.0, 9.0),
             ("repro.ckpt.write", 1.0, 8.8), ("repro.ckpt.hash", 1.0, 3.0),
             ("repro.ckpt.serialize", 3.0, 6.0),
             ("repro.ckpt.fsync", 6.0, 8.5)]
    modules = {0: [("jit_train_step(1)", 0.0, 1.0),
                   ("jit_train_step(1)", 9.0, 10.0)]}
    s = trace.summarize(spans, modules, {})
    assert dict(s.idle_by_span) == pytest.approx({
        "repro.ckpt.hash": 2.0, "repro.ckpt.serialize": 3.0,
        "repro.ckpt.fsync": 2.5, "repro.ckpt.write": 0.3, "wait": 0.2})
