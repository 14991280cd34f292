#!/usr/bin/env python3
"""Smoke run of the system's device paths on TPU chips.

    python chip_smoke.py             # one chip: engine phase, trainer phase
    python chip_smoke.py --chips 4   # four chips: the sharded engine only

Engine phase: ``run_cells(backend="jax", step="scan", mesh=None)`` on the
1M-peer fleet grid of ``benchmarks/fleet.py`` (10,000 class-pooled gossip
cells).  Every cell must satisfy wall = work + checkpoint + restore +
waste, a second run must repeat the first bit for bit, and on a subset of
the cells the means must agree with the numpy reference backend within the
bands of ``tests/test_p2p.py``.

Trainer phase: ``FaultTolerantTrainer`` on olmo-1b at its published widths
(d_model 2048, 16 heads x 128, d_ff 8192, vocab 50304), cut from 16 to 8
layers, at seq 2048 and batch 4.  The step's compiled memory must fit the
chip.  A three-step run saves at step 2; a second run resumes from that
checkpoint on disk and must reproduce the third step's loss exactly.

Four-chip phase: the same grid sharded over ``cell_mesh(4)`` must be
bitwise equal to the single-device run.

The timings printed are of one cold smoke run, not results.  Any failure
exits non-zero, and so does a host without a TPU.  The last line of a
passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ENGINE_CELLS = 10_000    # the fleet grid of benchmarks/fleet.py
ENGINE_REF_CELLS = 1_000  # cells also run on the numpy reference backend
# tests/test_p2p.py's jax-vs-numpy bands: means, relative.
ENGINE_BANDS = (("wall_time", 0.08), ("n_checkpoints", 0.15),
                ("n_failures", 0.15))

TRAIN_ARCH = "olmo-1b"
TRAIN_LAYERS = 8   # of 16: all 16 ask for 20.41 GB of the chip's 15.75 GB
TRAIN_SEQ = 2048
TRAIN_BATCH = 4


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"smoke check failed: {msg}")


def assert_same(a, b, what: str) -> None:
    """Every array of two engine BatchResults is bitwise equal."""
    import numpy as np

    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if isinstance(x, np.ndarray):
            check(np.array_equal(x, y), f"{what}: {f.name} differs")


# --------------------------------------------------------------------------- #
# Engine                                                                      #
# --------------------------------------------------------------------------- #

def engine_phase(n_cells: int = ENGINE_CELLS,
                 n_ref: int = ENGINE_REF_CELLS) -> None:
    import numpy as np

    from benchmarks.fleet import million_peer_cells
    from repro.sim import run_cells

    cells = million_peer_cells(n_cells)
    t0 = time.monotonic()
    res = run_cells(cells, backend="jax", step="scan", mesh=None)
    cold = time.monotonic() - t0
    t0 = time.monotonic()
    again = run_cells(cells, backend="jax", step="scan", mesh=None)
    warm = time.monotonic() - t0
    say(f"engine: {n_cells} cells of 1M-peer jobs, {res.n_steps} steps; "
        f"smoke timings: first run {cold:.2f} s (compile included), "
        f"second run {warm:.2f} s, compile ~{cold - warm:.2f} s")
    assert_same(res, again, "engine rerun")
    check(bool(res.completed.all()), "engine: some cells did not complete")
    total = (res.work_required + res.checkpoint_time + res.restore_time
             + res.wasted_work)
    err = float(np.max(np.abs(res.wall_time - total) / res.wall_time))
    check(err <= 1e-9, f"engine: wall != work+ckpt+restore+waste "
                       f"(max rel err {err:.3e})")
    say(f"engine: accounting identity holds in every cell "
        f"(max rel err {err:.3e})")

    t0 = time.monotonic()
    ref = run_cells(cells[:n_ref], backend="numpy")
    say(f"engine: numpy reference on {n_ref} cells "
        f"{time.monotonic() - t0:.2f} s")
    check(bool(ref.completed.all()), "engine: numpy reference incomplete")
    for field, rel in ENGINE_BANDS:
        got = float(np.mean(getattr(res, field)[:n_ref]))
        want = float(np.mean(getattr(ref, field)))
        say(f"engine: mean {field} jax {got!r} numpy {want!r} "
            f"(band {rel})")
        check(abs(got - want) <= rel * abs(want),
              f"engine: mean {field} outside the band")


def sharded_engine_phase(n_dev: int, n_cells: int = ENGINE_CELLS) -> None:
    from benchmarks.fleet import million_peer_cells
    from repro.distributed.mesh import cell_mesh
    from repro.sim import run_cells

    cells = million_peer_cells(n_cells)
    t0 = time.monotonic()
    single = run_cells(cells, backend="jax", step="scan", mesh=None)
    t_single = time.monotonic() - t0
    t0 = time.monotonic()
    sharded = run_cells(cells, backend="jax", step="scan",
                        mesh=cell_mesh(n_dev))
    t_sharded = time.monotonic() - t0
    say(f"sharded engine: {n_cells} cells; smoke timings (compile "
        f"included): one device {t_single:.2f} s, {n_dev} devices "
        f"{t_sharded:.2f} s")
    assert_same(single, sharded, f"{n_dev}-device vs one-device")
    check(bool(single.completed.all()), "sharded engine: cells incomplete")
    say(f"sharded engine: {n_dev}-device results bitwise equal to "
        f"one device")


# --------------------------------------------------------------------------- #
# Trainer                                                                     #
# --------------------------------------------------------------------------- #

def trainer_phase(cfg, seq_len: int, batch: int, ckpt_root: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.ckpt import AsyncCheckpointer
    from repro.data import DataConfig
    from repro.runtime import CheckpointPolicyConfig, FaultTolerantTrainer
    from repro.train.step import init_train_state

    ckpt = AsyncCheckpointer(ckpt_root, n_shards=4)
    try:
        trainer = FaultTolerantTrainer(
            cfg, DataConfig(vocab=cfg.vocab, seq_len=seq_len,
                            global_batch=batch),
            ckpt=ckpt,
            policy=CheckpointPolicyConfig(kind="fixed", fixed_interval=2.0))

        state = jax.eval_shape(lambda k: init_train_state(k, cfg),
                               jax.random.key(0))
        state_bytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(state))
        tok = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)
        t0 = time.monotonic()
        mem = trainer.train_step.lower(
            state, {"tokens": tok, "labels": tok}).compile().memory_analysis()
        compile_s = time.monotonic() - t0
        peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        say(f"trainer: train step compiled in {compile_s:.1f} s; "
            f"state {state_bytes / 1e9:.3f} GB, argument "
            f"{mem.argument_size_in_bytes / 1e9:.3f} GB, output "
            f"{mem.output_size_in_bytes / 1e9:.3f} GB (aliased "
            f"{mem.alias_size_in_bytes / 1e9:.3f} GB), temp "
            f"{mem.temp_size_in_bytes / 1e9:.3f} GB, peak {peak / 1e9:.3f} GB")
        stats = jax.devices()[0].memory_stats() or {}
        limit = stats.get("bytes_limit")
        if limit is not None:
            say(f"trainer: device memory limit {limit / 1e9:.3f} GB")
            check(peak <= limit, "trainer: train step does not fit the chip")
        free = shutil.disk_usage(ckpt_root).free
        check(free > 1.2 * state_bytes,
              f"trainer: {free / 1e9:.1f} GB free under {ckpt_root}, "
              f"a checkpoint takes {state_bytes / 1e9:.1f} GB")

        t0 = time.monotonic()
        first = trainer.run(n_steps=3)
        t_first = time.monotonic() - t0
        check(first.steps_completed == 3 and len(first.losses) == 3,
              "trainer: first run did not take 3 steps")
        check(first.n_checkpoints == 1, "trainer: expected one save")
        check(all(np.isfinite(first.losses)), "trainer: non-finite loss")
        say(f"trainer: 3 steps with one save, smoke timing {t_first:.1f} s "
            f"(state init and step compile included); save blocked "
            f"{ckpt.last_blocking_seconds:.2f} s, write "
            f"{ckpt.last_write_seconds:.2f} s; losses {first.losses!r}")

        t0 = time.monotonic()
        resumed = trainer.run(n_steps=3, resume=True)
        t_resumed = time.monotonic() - t0
        check(ckpt.last_restored is not None,
              "trainer: resume found no checkpoint")
        step, path = ckpt.last_restored
        check(step == 2 and os.path.isdir(path)
              and Path(path).parent == Path(ckpt_root),
              f"trainer: restored {ckpt.last_restored!r}, expected step 2 "
              f"under {ckpt_root}")
        check(len(resumed.losses) == 1,
              f"trainer: resumed run took {len(resumed.losses)} steps, "
              f"expected 1 after the step-2 image")
        say(f"trainer: resumed from {path} (restore "
            f"{ckpt.last_restore_seconds:.2f} s, run {t_resumed:.1f} s); "
            f"step-3 loss {resumed.losses[0]!r} vs uninterrupted "
            f"{first.losses[2]!r}")
        check(resumed.losses[0] == first.losses[2],
              "trainer: loss after the restore differs from the "
              "uninterrupted run")
    finally:
        ckpt.close()


# --------------------------------------------------------------------------- #
# Entry point                                                                 #
# --------------------------------------------------------------------------- #

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded engine over four chips")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              f"nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.launch.compile_cache import enable_compile_cache

    say(f"compile cache: {enable_compile_cache()}")
    say(f"device: {devices[0].device_kind} x {len(devices)}")

    if args.chips == 4:
        sharded_engine_phase(4)
    else:
        engine_phase()

        from repro.configs import get_config

        full = get_config(TRAIN_ARCH)
        cfg = dataclasses.replace(full, n_layers=TRAIN_LAYERS)
        say(f"trainer: {TRAIN_ARCH} at published widths (d_model "
            f"{cfg.d_model}, {cfg.attention.n_heads} heads x "
            f"{cfg.attention.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab}); "
            f"depth cut {full.n_layers} -> {cfg.n_layers} layers because "
            f"all {full.n_layers} need 20.41 GB of the chip's 15.75 GB "
            f"(bf16 params + f32 AdamW state); seq {TRAIN_SEQ}, "
            f"batch {TRAIN_BATCH}")
        ckpt_root = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
        try:
            trainer_phase(cfg, TRAIN_SEQ, TRAIN_BATCH, ckpt_root)
        finally:
            shutil.rmtree(ckpt_root, ignore_errors=True)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
