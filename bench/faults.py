"""Controls and faults planted under an engine cell's timed path.

Each entry of ``ENGINE`` takes the cell's configuration and gives a
stand-in for ``repro.sim.run_cells``, ``f(real_run_cells, cells, kw)``;
``planted(f)`` puts it in place for a whole run.  ``SHARDED`` holds the
faults that only a cell of several chips can have; they break the calls
that pass a mesh and leave the others alone.  ``bench/control.py`` reads
them on the chip at the cell's own size, where they set the upper
readings of the limits, and ``bench/tests/test_correct.py`` and
``bench/tests/test_four_devices.py`` see each turn ``correct`` false on
the CPU.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict

import numpy as np

from bench.reference import ckpt_process


@contextlib.contextmanager
def planted(breakage: Callable):
    import repro.sim as sim

    real = sim.run_cells
    sim.run_cells = lambda cells, **kw: breakage(real, cells, kw)
    try:
        yield
    finally:
        sim.run_cells = real


def _rate(config: Dict[str, Any]) -> float:
    return float(config["peers_per_job"]) / float(config["peer_mtbf_s"])


def control_f32(config):
    """The plain reference in the program's place, in float32: the
    precision below the float64 that the configuration states."""
    def f(real, cells, kw):
        res = real(cells, **kw)
        r = ckpt_process.simulate(
            len(cells), int(cells[0].seed), work=float(config["work_s"]),
            v=float(config["checkpoint_s"]), t_d=float(config["restore_s"]),
            rate=_rate(config), dtype=np.float32)
        return dataclasses.replace(
            res, wall_time=r["wall"], work_required=r["work"],
            n_checkpoints=r["n_checkpoints"], n_failures=r["n_failures"],
            wasted_work=r["waste"], checkpoint_time=r["ckpt"],
            restore_time=r["restore"], completed=np.ones(len(cells), bool))
    return f


def rate_x2(config):
    """The jobs' peers fail twice as often as the configuration states."""
    from repro.sim import scenario

    scen = scenario("constant", mtbf=float(config["peer_mtbf_s"]) / 2.0)

    def f(real, cells, kw):
        return real([dataclasses.replace(c, scenario=scen) for c in cells],
                    **kw)
    return f


def interval_x2(config):
    """Checkpoints at twice the Eq. 11 interval for the stated rate."""
    from repro.sim import PolicyConfig

    pol = PolicyConfig(kind="fixed", fixed_T=2.0 * ckpt_process.interval(
        _rate(config), float(config["checkpoint_s"])))

    def f(real, cells, kw):
        return real([dataclasses.replace(c, policy=pol) for c in cells], **kw)
    return f


def state_unchanged(config):
    """No step taken: every cell left where it started."""
    return lambda real, cells, kw: real(cells, **dict(kw, max_steps=0))


def answer_altered(config):
    """One cell's wall time off by a second where it is produced."""
    def f(real, cells, kw):
        res = real(cells, **kw)
        res.wall_time[len(cells) // 2] += 1.0
        return res
    return f


def half_left_out(config):
    """Half of the cells never simulated; zeros come back for them."""
    def f(real, cells, kw):
        res = real(cells[: len(cells) // 2], **kw)
        return dataclasses.replace(res, **{
            fld.name: np.concatenate([getattr(res, fld.name),
                                      np.zeros_like(getattr(res, fld.name))])
            for fld in dataclasses.fields(res)
            if isinstance(getattr(res, fld.name), np.ndarray)})
    return f


ENGINE = {"control_f32": control_f32, "fault_rate_x2": rate_x2,
          "fault_interval_x2": interval_x2,
          "fault_state_unchanged": state_unchanged,
          "fault_answer_altered": answer_altered,
          "fault_half_left_out": half_left_out}


def shards_swapped(config):
    """The results of the first two shards swapped as they come back from
    the chips: each cell's fields stay together, in another cell's place."""
    def f(real, cells, kw):
        res = real(cells, **kw)
        mesh = kw.get("mesh")
        if mesh is None:
            return res
        per = -(-len(cells) // mesh.size)
        order = np.arange(len(cells))
        order[:2 * per] = np.roll(order[:2 * per], per)
        return dataclasses.replace(res, **{
            fld.name: getattr(res, fld.name)[order]
            for fld in dataclasses.fields(res)
            if isinstance(getattr(res, fld.name), np.ndarray)})
    return f


def exchange_left_out(config):
    """The exchange between chips left out: the chunk's psum of the
    unfinished count is the local count, so the host reads the first
    shard's and ends the sweep once that shard's cells are done."""
    broken_programs: Dict[Any, Any] = {}

    def f(real, cells, kw):
        if kw.get("mesh") is None:
            return real(cells, **kw)
        import jax
        import repro.sim.engine as eng

        saved = eng._SHARDED_CACHE, jax.lax.psum
        eng._SHARDED_CACHE = broken_programs
        jax.lax.psum = lambda x, *a, **k: x
        try:
            return real(cells, **kw)
        finally:
            eng._SHARDED_CACHE, jax.lax.psum = saved
    return f


SHARDED = {"fault_shards_swapped": shards_swapped,
           "fault_exchange_left_out": exchange_left_out}
