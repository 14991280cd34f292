"""The Monte-Carlo engine under the benchmark: closed-loop sweeps through
run_cells on the cell's chips.

A one-chip cell runs on one device (``mesh=None``), also on a host with
more; a cell of several chips shards each sweep's cells over them through
the program's own ``cell_mesh``, as ``run_cells(mesh="auto")`` does on a
host with that many.  Set-up runs one sweep of the cell's size, which
compiles (or loads) the only program the window uses.  The window then
runs sweeps back to back, each of ``cells_per_sweep`` cells with fresh
seeds derived from ``--seed`` and the sweep's index, through
``repro.sim.run_cells``.

Checks, over every cell of every sweep in the window:

* the configuration's guarantees on each cell: wall = work + checkpoint +
  restore + waste to the limit the configuration states for float64;
  every cell completes; every cell did the configuration's work;
  checkpoint time is exactly V per checkpoint;
* against the plain reference of the checkpoint process
  (``bench/reference/ckpt_process.py``: the Eq. 11 interval at the
  configured failure rate, nothing of the program), run after the window
  on ``reference_cells`` cells drawn from the seed: each sweep's mean wall
  time, checkpoints, failures and waste, as a relative gap to the
  reference's mean; the largest over the sweeps is compared;
* on several chips, the window's first sweep against the same cells run
  again on one device after the window, one chip's share of them at a
  time (the one-chip cell's program where the shares are equal): every
  result field of every cell must be equal bit for bit.  All cells of a
  sweep share one configuration, so a cell's results in another cell's
  place pass every check above; this one sees them.
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys
import time
from typing import Any, Dict, List

import numpy as np

from bench import fleet_cells, seeds
from bench.compare import check
from bench.harness import Outcome
from bench.reference import ckpt_process

SEED_TAG = 0x656E67   # "eng"
REF_TAG = 0x726566    # "ref"
# Compared means: the program's field, the reference's, the check's name.
MEANS = (("wall_time", "wall", "mean_wall_gap"),
         ("n_checkpoints", "n_checkpoints", "mean_checkpoints_gap"),
         ("n_failures", "n_failures", "mean_failures_gap"),
         ("wasted_work", "waste", "mean_waste_gap"))


@contextlib.contextmanager
def _spans_inside_run_cells(rec):
    """Time the engine's pack and device run from outside the program."""
    import repro.sim.engine as eng

    pack, run_jax = eng._pack, eng._run_jax

    def timed_pack(*a, **kw):
        with rec.span("engine.pack"):
            return pack(*a, **kw)

    def timed_run(*a, **kw):
        with rec.span("engine.run"):
            return run_jax(*a, **kw)

    eng._pack, eng._run_jax = timed_pack, timed_run
    try:
        yield
    finally:
        eng._pack, eng._run_jax = pack, run_jax


def accounting_error(res) -> float:
    """Largest |wall - (work + ckpt + restore + waste)| / wall, in float64."""
    parts = (res.work_required.astype(np.float64)
             + res.checkpoint_time.astype(np.float64)
             + res.restore_time.astype(np.float64)
             + res.wasted_work.astype(np.float64))
    wall = res.wall_time.astype(np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        err = np.abs(wall - parts) / np.abs(wall)
    return float(np.max(np.nan_to_num(err, nan=np.inf)))


def cell_checks(results: List[Any], config: Dict[str, Any]
                ) -> Dict[str, Dict[str, Any]]:
    limits = config["limits"]
    v, work = float(config["checkpoint_s"]), float(config["work_s"])
    acct = max(accounting_error(r) for r in results)
    incomplete = sum(int((~np.asarray(r.completed)).sum()) for r in results)
    work_gap = max(float(np.max(np.abs(r.work_required - work)))
                   for r in results)
    ckpt_gap = max(float(np.max(np.abs(r.checkpoint_time
                                       - r.n_checkpoints * v)))
                   for r in results)
    return {
        "accounting_rel_err": check(acct, limits["accounting_rel_err"]),
        "cells_incomplete": check(incomplete, 0),
        "work_gap_s": check(work_gap, 0.0),
        "ckpt_time_gap_s": check(ckpt_gap, 0.0),
    }


def cells_differing(got, want) -> int:
    """Cells whose results differ from ``want``'s in any field, bit for
    bit; every cell when the fields' shapes or types differ."""
    n = len(want.wall_time)
    differ = np.zeros(n, bool)
    for fld in dataclasses.fields(want):
        b = getattr(want, fld.name)
        if not isinstance(b, np.ndarray):
            continue
        a = np.asarray(getattr(got, fld.name))
        if a.shape != b.shape or a.dtype != b.dtype:
            return n
        bits = np.dtype(f"u{b.dtype.itemsize}")
        differ |= (np.ascontiguousarray(a).view(bits)
                   != np.ascontiguousarray(b).view(bits))
    return int(differ.sum())


def shard(res, k: int, per: int):
    """Cells ``[k * per, (k + 1) * per)`` of a batch's results."""
    return dataclasses.replace(res, **{
        fld.name: getattr(res, fld.name)[k * per:(k + 1) * per]
        for fld in dataclasses.fields(res)
        if isinstance(getattr(res, fld.name), np.ndarray)})


def one_device_check(run_cells, cells, got, chips: int, step: str):
    """``cells``, whose sharded results are ``got``, again on one device,
    one chip's share at a time: the cells that differ bit for bit, and the
    engine steps each share ran to."""
    per = -(-len(cells) // chips)
    differing, steps = 0, []
    for k in range(chips):
        one = run_cells(cells[k * per:(k + 1) * per], backend="jax",
                        step=step, mesh=None)
        differing += cells_differing(shard(got, k, per), one)
        steps.append(int(one.n_steps))
    return differing, steps


def reference(config: Dict[str, Any], seed: int) -> Dict[str, np.ndarray]:
    """The plain checkpoint process on ``reference_cells`` cells."""
    return ckpt_process.simulate(
        int(config["reference_cells"]), seeds.derive(seed, REF_TAG),
        work=float(config["work_s"]), v=float(config["checkpoint_s"]),
        t_d=float(config["restore_s"]),
        rate=float(config["peers_per_job"]) / float(config["peer_mtbf_s"]))


def mean_gaps(results: List[Any], ref: Dict[str, np.ndarray]
              ) -> Dict[str, float]:
    """Per compared mean, the largest |sweep mean / reference mean - 1|."""
    gaps = {}
    for field, ref_field, name in MEANS:
        want = float(np.mean(ref[ref_field].astype(np.float64)))
        gaps[name] = max(
            abs(float(np.mean(np.asarray(getattr(r, field), np.float64)))
                / want - 1.0) for r in results)
    return gaps


def run(cell, *, seed: int, window, rec, dev) -> Outcome:
    from repro.sim import run_cells

    config, traffic = cell.config, cell.traffic
    n = int(traffic["cells_per_sweep"])
    base = seeds.derive(seed, SEED_TAG, bits=30)
    if cell.chips > 1:
        from repro.distributed.mesh import cell_mesh

        mesh = cell_mesh(cell.chips)
    else:
        mesh = None

    def sweep_cells(j: int):
        with rec.span("engine.cells"):
            return fleet_cells.engine_cells(
                config, traffic, fleet_cells.sweep_seeds(base, j, n))

    def sweep(j: int):
        return run_cells(sweep_cells(j), backend="jax", step=config["step"],
                         mesh=mesh)

    t_setup = time.monotonic()
    sweep(0)                      # set-up: the window's one program
    print(f"bench: set-up {t_setup - window.t_process:.1f} s to the first "
          f"sweep, which took {time.monotonic() - t_setup:.1f} s",
          file=sys.stderr)
    results: List[Any] = []
    log: List[tuple] = []     # (start, end, cells completed) per sweep
    with _spans_inside_run_cells(rec):
        window.open()
        j = 1
        while True:
            t0 = time.monotonic()
            with rec.span("sweep"):
                results.append(sweep(j))
            log.append((t0, time.monotonic(),
                        int(np.asarray(results[-1].completed).sum())))
            j += 1
            if not window.tick():
                break
        window.close()
    memory_peak = dev.memory_peak_bytes()

    done = sum(int(np.asarray(r.completed).sum()) for r in results)
    checks = cell_checks(results, config)
    t_ref = time.monotonic()
    gaps = mean_gaps(results, reference(config, seed))
    print(f"bench: reference {time.monotonic() - t_ref:.1f} s over "
          f"{config['reference_cells']} cells; {len(results)} sweeps",
          file=sys.stderr)
    limits = config["limits"]
    checks.update({k: check(v, limits[k]) for k, v in gaps.items()})
    if mesh is not None:
        t_one = time.monotonic()
        differing, steps = one_device_check(
            run_cells, sweep_cells(1), results[0], cell.chips, config["step"])
        checks["cells_differing_from_one_device"] = check(differing, 0)
        print(f"bench: the first window sweep again on one device, a "
              f"chip's share at a time, {time.monotonic() - t_one:.1f} s; "
              f"engine steps {results[0].n_steps} sharded, {steps} alone",
              file=sys.stderr)
    attempted = n * len(results)
    return Outcome(
        e2e={"engine_cells_per_s": done / window.elapsed},
        ctx={"sweeps_log": log, "rec": rec},
        checks=checks, attempted=attempted, failed=attempted - done,
        memory_peak_bytes=memory_peak)
