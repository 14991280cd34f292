"""The reader of ``ckpt_hash_threads_per_save``: threads per save, and
None where the program counts no hashing threads (a program older than the
counter) or made no save."""
import sys

import pytest

from bench import registry
from repro import tracing


def test_hash_threads_reader_divides_by_the_saves(monkeypatch):
    t = tracing.Tracer()
    monkeypatch.setattr(tracing, "counters", t.counters)
    read = registry.metric_reader("ckpt_hash_threads_per_save").read
    assert read({}) is None                  # nothing counted yet
    t.count("ckpt.saves")
    assert read({}) is None                  # a program without the counter
    t.count("ckpt.hash_threads", 8)
    t.count("ckpt.saves")
    t.count("ckpt.hash_threads", 4)
    assert read({}) == pytest.approx(6.0, rel=1e-12)
    import repro

    monkeypatch.delattr(repro, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert read({}) is None


def test_hash_threads_reader_needs_a_save(monkeypatch):
    t = tracing.Tracer()
    monkeypatch.setattr(tracing, "counters", t.counters)
    t.count("ckpt.hash_threads", 1)
    assert registry.metric_reader("ckpt_hash_threads_per_save").read({}) is None
