"""Program spans and counters (repro.tracing) and where the program records
them: the checkpoint writer, the trainer loop and the engine's host path."""
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.ckpt import AsyncCheckpointer, latest_checkpoint, store
from repro.configs import get_smoke_config
from repro.data import DataConfig
from repro.runtime import CheckpointPolicyConfig, FaultTolerantTrainer
from repro.sim import CellSpec, PolicyConfig, run_cells, scenario
from repro.sim import engine as E
from repro.train.step import init_train_state


def _since(t0, name=None, tracer=tracing):
    got = tracer.spans(t0)
    assert got is not None
    return [s for s in got if name is None or s.name == name]


def _delta(before, name):
    return tracing.counters().get(name, 0) - before.get(name, 0)


# --------------------------------------------------------------- the module
def test_nesting_parents_and_ids():
    t = tracing.Tracer()
    with t.span("outer", id=7) as outer:
        with t.span("mid") as mid:
            with t.span("inner", id=9) as inner:
                pass
        with t.span("sibling") as sib:
            pass
    assert outer.parent is None and outer.id == 7
    assert mid.parent == outer.seq and mid.id == 7      # id inherited
    assert inner.parent == mid.seq and inner.id == 9    # id given
    assert sib.parent == outer.seq
    # The ring holds spans in the order they closed.
    assert [s.name for s in t.spans()] == ["inner", "mid", "sibling", "outer"]
    assert all(s.t0 <= s.t1 for s in t.spans())
    assert outer.t0 <= mid.t0 and inner.t1 <= mid.t1 <= outer.t1
    assert outer.seconds == outer.t1 - outer.t0
    assert t._stack() == []


def test_a_span_on_another_thread_names_its_cause():
    t = tracing.Tracer()
    got = {}

    def work(cause):
        with t.span("child", parent=cause) as sp:
            with t.span("grandchild") as g:
                pass
        got["child"], got["grandchild"] = sp, g

    with t.span("request", id=3) as req:
        th = threading.Thread(target=work, args=(req,))
        th.start()
        th.join(timeout=10)
    assert not th.is_alive()
    child, grand = got["child"], got["grandchild"]
    assert child.parent == req.seq and child.id == 3
    assert child.thread != req.thread
    assert grand.parent == child.seq and grand.id == 3


def test_a_span_closes_when_its_body_raises():
    t = tracing.Tracer()
    with pytest.raises(KeyError):
        with t.span("outer"):
            with t.span("fails"):
                raise KeyError("x")
    names = [s.name for s in t.spans()]
    assert names == ["fails", "outer"]
    assert all(s.t1 is not None for s in t.spans())
    assert t._stack() == []


def test_counters_add_up():
    t = tracing.Tracer()
    assert t.count("a") == 1
    assert t.count("a", 4) == 5     # the new total
    t.count("b", 0)
    assert t.counters() == {"a": 5, "b": 0}
    snapshot = t.counters()
    t.count("a")
    assert snapshot["a"] == 5   # a copy, not a live view


def test_a_reader_gets_none_once_the_ring_has_dropped_part_of_its_window():
    t = tracing.Tracer(capacity=4)
    for i in range(4):
        with t.span("s", id=i):
            pass
    first = t.spans()
    assert [s.id for s in first] == [0, 1, 2, 3]
    with t.span("s", id=4):
        pass
    # Span 0 was dropped: a window reaching back to it cannot be read ...
    assert t.spans() is None
    assert t.spans(first[0].t0) is None
    # ... and one that starts after it ended still can.
    assert [s.id for s in t.spans(first[1].t0)] == [1, 2, 3, 4]


def test_counters_and_ring_lose_nothing_under_contention():
    t = tracing.Tracer(capacity=100_000)
    n_threads, n_each = 16, 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(k):
            for i in range(n_each):
                with t.span("w", id=k):
                    t.count("n")
                    t.count("bytes", 3)

        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    assert t.counters() == {"n": n_threads * n_each,
                            "bytes": 3 * n_threads * n_each}
    held = t.spans()
    assert len(held) == n_threads * n_each
    assert len({s.seq for s in held}) == len(held)
    assert all(s.parent is None for s in held)


# --------------------------------------------------------- checkpoint path
@pytest.fixture()
def tree():
    k = jax.random.key(0)
    return {"params": {"w": jax.random.normal(k, (256, 512)),
                       "b": jnp.zeros((512,), jnp.bfloat16)},
            "opt": {"m": jnp.ones((256, 512)), "step": jnp.int32(7)}}


def test_save_and_write_phases_cover_the_write(tmp_path, tree):
    before = tracing.counters()
    start = time.monotonic()
    ck = AsyncCheckpointer(str(tmp_path / "p"), n_shards=2)
    blocking = ck.save(5, tree)
    ck.wait()
    ck.close()
    spans = _since(start)
    by = lambda name: [s for s in spans if s.name == name and s.id == 5]
    (save,) = by("ckpt.save")
    (write,) = by("ckpt.write")
    assert blocking == ck.last_blocking_seconds == save.seconds
    assert ck.last_write_seconds == write.seconds
    assert {s.name for s in spans if s.parent == save.seq} == {
        "ckpt.snapshot", "ckpt.enqueue", "ckpt.write"}
    assert write.parent == save.seq and write.thread != save.thread
    # The explicit wait, then the one inside close.
    wait = min((s for s in spans if s.name == "ckpt.wait"),
               key=lambda s: s.t0)
    assert wait.t0 >= save.t1 and wait.t1 >= write.t1

    parts = [s for s in spans if s.parent == write.seq]
    names = sorted(s.name for s in parts)
    # Two shards and the manifest: a body and an fsync each.
    assert names == ["ckpt.commit", "ckpt.fsync", "ckpt.fsync", "ckpt.fsync",
                     "ckpt.hash", "ckpt.serialize", "ckpt.serialize",
                     "ckpt.serialize"]
    covered = sum(s.seconds for s in parts)
    assert covered <= write.seconds
    assert covered >= write.seconds - max(0.05 * write.seconds, 0.02)
    (commit,) = by("ckpt.commit")
    # The marker's body and fsync, then the two directory fsyncs.
    assert sorted(s.name for s in spans if s.parent == commit.seq) == [
        "ckpt.fsync", "ckpt.fsync", "ckpt.fsync", "ckpt.serialize"]

    path = os.path.join(str(tmp_path / "p"), "step_00000005")
    on_disk = sum(e.stat().st_size for e in os.scandir(path))
    assert _delta(before, "ckpt.saves") == 1
    assert _delta(before, "ckpt.bytes_written") == on_disk


def test_a_save_counts_the_threads_that_hashed_it(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path / "p"), n_shards=2)
    before = tracing.counters()
    ck.save(1, {"w": np.zeros((4, 4), np.float32)})
    ck.wait()
    assert _delta(before, "ckpt.hash_threads") == 1      # one leaf
    cpus = store._cpus()
    n = cpus + 1
    big = {f"w{i}": np.full(((16 << 20) // (4 * n)) + 1, i, np.float32)
           for i in range(n)}
    before = tracing.counters()
    ck.save(2, big)
    ck.wait()
    ck.close()
    threads = _delta(before, "ckpt.hash_threads")
    assert threads == cpus
    if cpus > 1:
        assert threads > 1


def test_replicas_and_restore_fallbacks_are_spanned(tmp_path, tree):
    before = tracing.counters()
    start = time.monotonic()
    primary = str(tmp_path / "primary")
    ck = AsyncCheckpointer(primary, replicas=[str(tmp_path / "r0"),
                                              str(tmp_path / "r1")],
                           n_shards=2)
    ck.save(3, tree)
    ck.wait()
    (write,) = _since(start, "ckpt.write")
    reps = [s for s in _since(start, "ckpt.replicate")
            if s.parent == write.seq]
    assert len(reps) == 2 and all(s.id == 3 for s in reps)
    _, path = latest_checkpoint(primary)
    image = sum(e.stat().st_size for e in os.scandir(path))
    # The store's own writes; the two replica copies are not counted.
    assert _delta(before, "ckpt.bytes_written") == image

    for name in os.listdir(path):
        if name.startswith("shard_"):
            with open(os.path.join(path, name), "wb") as f:
                f.write(b"not a checkpoint shard")
    step, _ = ck.restore_latest(tree)
    ck.close()
    # The corrupt primary was skipped for a replica, inside one span.
    assert step == 3 and ck.last_restored[1] != path
    (restore,) = _since(start, "ckpt.restore")
    assert ck.last_restore_seconds == restore.seconds


# ------------------------------------------------------------ trainer loop
def _tiny_trainer(tmp_path, every=None):
    cfg = get_smoke_config("olmo-1b")
    ck = AsyncCheckpointer(str(tmp_path / "ckpt"), n_shards=1)
    policy = CheckpointPolicyConfig(
        kind="fixed", fixed_interval=float(every) if every else float("inf"))
    return FaultTolerantTrainer(
        cfg, DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=2, seed=1),
        ckpt=ck, policy=policy)


def test_trainer_spans_each_step(tmp_path):
    tr = _tiny_trainer(tmp_path, every=3)
    start = time.monotonic()
    report = tr.run(n_steps=5)
    tr.ckpt.close()
    spans = _since(start)
    for name in ("train.batch", "train.step", "train.loss", "train.decide"):
        assert sorted(s.id for s in spans if s.name == name) == list(range(5))
    assert [s.id for s in spans if s.name == "ckpt.save"] == [3]
    assert report.steps_completed == 5
    # Within a step the spans follow each other.
    for i in range(5):
        b, st, lo, d = (next(s for s in spans if s.name == n and s.id == i)
                        for n in ("train.batch", "train.step", "train.loss",
                                  "train.decide"))
        assert b.t1 <= st.t0 and st.t1 <= lo.t0 and lo.t1 <= d.t0


def test_trainer_spans_close_when_the_step_raises(tmp_path):
    tr = _tiny_trainer(tmp_path)
    step_fn, calls = tr.train_step, []

    class Stop(Exception):
        pass

    def stopping_step(state, batch):
        calls.append(1)
        if len(calls) == 3:
            raise Stop
        return step_fn(state, batch)

    tr.train_step = stopping_step
    start = time.monotonic()
    with pytest.raises(Stop):
        tr.run(n_steps=10)
    tr.ckpt.close()
    steps = _since(start, "train.step")
    assert sorted(s.id for s in steps) == [0, 1, 2]
    assert all(s.t1 is not None for s in steps)
    assert tracing._TRACER._stack() == []


def test_trainer_spans_failures_and_restores(tmp_path):
    from repro.runtime import FailureInjector
    from repro.sim.network import constant_mtbf

    cfg = get_smoke_config("olmo-1b")
    inj = FailureInjector(k=8, mtbf_fn=constant_mtbf(2000.0),
                          seconds_per_step=120.0, seed=0)
    tr = FaultTolerantTrainer(
        cfg, DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=4, seed=1),
        ckpt=AsyncCheckpointer(str(tmp_path / "ckpt"), n_shards=2),
        injector=inj,
        policy=CheckpointPolicyConfig(kind="adaptive", prior_mtbf=2000.0,
                                      prior_v=5.0, min_interval=30.0),
        virtual_ckpt_overhead=5.0, virtual_restore_time=12.0)
    start = time.monotonic()
    report = tr.run(n_steps=30)
    tr.ckpt.close()
    assert report.n_failures > 0
    restores = _since(start, "train.restore")
    assert len(restores) == report.n_failures
    # A failed step's span is kept, so there are more step spans than steps
    # taken; each restore shares its id with the step that failed.
    steps = _since(start, "train.step")
    assert len(steps) == len(report.losses) + report.n_failures
    for r in restores:
        assert any(s.id == r.id and s.t1 <= r.t0 for s in steps)


def test_the_train_step_program_has_a_stable_name(tmp_path):
    tr = _tiny_trainer(tmp_path)
    state = jax.eval_shape(lambda: init_train_state(jax.random.key(0),
                                                    tr.cfg))
    batch = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         tr.data.batch_at(0))
    text = tr.train_step.lower(state, batch).as_text()
    tr.ckpt.close()
    assert text.startswith("module @jit_train_step ")


# ------------------------------------------------------- engine host path
def _engine_cells(n=6, work=4 * 3600.0):
    return [CellSpec(scenario=scenario("constant", mtbf=4000.0),
                     policy=PolicyConfig(kind="fixed", fixed_T=900.0),
                     seed=s, k=4, work=work) for s in range(n)]


@pytest.mark.parametrize("mesh", ["none", "sharded"])
def test_run_cells_spans_chunks_and_syncs(mesh):
    from repro.distributed.mesh import cell_mesh

    chunk = 16
    before = tracing.counters()
    start = time.monotonic()
    res = run_cells(_engine_cells(), backend="jax", chunk=chunk,
                    mesh=None if mesh == "none" else cell_mesh(1))
    spans = _since(start)
    (call,) = [s for s in spans if s.name == "sim.run_cells"]
    mine = [s for s in spans if s.id == call.id]
    n_chunks = res.n_steps // chunk
    assert n_chunks >= 2 and res.n_steps % chunk == 0
    assert _delta(before, "sim.host_syncs") == n_chunks
    assert _delta(before, "sim.run_cells") == 1
    assert call.id == tracing.counters()["sim.run_cells"]
    count = lambda name: sum(s.name == name for s in mine)
    assert count("sim.pack") == count("sim.upload") == 1
    assert count("sim.download") == 1
    assert count("sim.chunk") == count("sim.sync") == n_chunks
    assert all(s.parent == call.seq for s in mine if s is not call)


def test_run_cells_numpy_backend_spans_the_call_and_pack():
    before = tracing.counters()
    start = time.monotonic()
    res = run_cells(_engine_cells(n=3), backend="numpy")
    names = [s.name for s in _since(start)]
    assert names.count("sim.run_cells") == names.count("sim.pack") == 1
    assert "sim.chunk" not in names and "sim.upload" not in names
    assert res.n_steps > 0
    assert _delta(before, "sim.run_cells") == 1
    assert _delta(before, "sim.host_syncs") == 0


def test_the_engine_chunk_programs_have_a_stable_name():
    from repro.distributed.mesh import cell_mesh

    cells = _engine_cells(n=2)
    with jax.enable_x64(True):
        run_cells(cells, backend="jax", chunk=16, mesh=None)
        run_cells(cells, backend="jax", chunk=16, mesh=cell_mesh(1))
        p = E._Params(*(jnp.asarray(a) for a in E._pack(cells)))
        s = E._init_state(p, jnp, 1)
        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(2, dtype=jnp.uint32))
        plain = E._jax_chunk_jit.lower((s, keys), p, 0.05, False, False,
                                       False, False, 1, 16).as_text()
        sharded = [fn.lower(s, keys, p).as_text()
                   for fn in E._SHARDED_CACHE.values()]
    assert plain.startswith("module @jit_engine_chunk ")
    assert sharded and all(t.startswith("module @jit_engine_chunk ")
                           for t in sharded)
