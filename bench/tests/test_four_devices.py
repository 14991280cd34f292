"""Engine cells on four virtual CPU devices, in a child process, since
``--xla_force_host_platform_device_count`` has to be set before JAX
starts.  The four-chip cell shards each sweep over the four devices and
passes ``correct``; with two shards' results swapped, or with the exchange
between the devices left out, it does not; and a one-chip cell still runs
on one device (``mesh=None``) where four are visible.  Sweeps of 16
cells, through ``harness.run_cell`` as on the chip, in chunks of 32 steps:
with the cell's 256, the shards of a sweep this small mostly end in the
same chunk, where leaving out the exchange changes no result."""
import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
FOUR = "engine-paper-normal.sweep-1k-4chip"
ONE = "engine-paper-normal.sweep-256"
SEED = 2 ** 33 + 12345
CELLS = 16


def _tiny(name):
    from bench import registry
    # The 64-cell sweeps' limits hold for 16 cells: these read at most
    # wall 9.2e-3, checkpoints 3.8e-2, failures 3.6e-2, waste 6.9e-2 over
    # seeds 0-7 and SEED.
    from bench.tests.test_correct import ENGINE_TINY_LIMITS

    real = registry.resolve(name)
    cfg = dict(real.config, reference_cells=2000,
               limits=dict(real.config["limits"], **ENGINE_TINY_LIMITS))
    return dataclasses.replace(real, config=cfg, traffic=dict(
        real.traffic, cells_per_sweep=CELLS))


def _run(cell, seed):
    """One run on the four devices; the result line, and the ``mesh`` of
    every ``run_cells`` call, as the device count of its mesh or None, and
    its number of cells."""
    from bench import faults, harness
    from bench.tests.test_correct import CpuChips

    meshes, sizes = [], []

    def spy(real, cells, kw):
        mesh = kw.get("mesh")
        meshes.append(None if mesh is None else mesh.size)
        sizes.append(len(cells))
        return real(cells, **kw)

    class Chips(CpuChips):
        count = 4

    with faults.planted(spy):
        result, _ = harness.run_cell(cell, seed=seed, seconds=1.0,
                                     trace=False, t_process=time.monotonic(),
                                     dev=Chips())
    return dict(result, meshes=meshes, sizes=sizes)


def child(seed: int) -> dict:
    import jax

    from bench import faults

    assert len(jax.devices()) == 4, jax.devices()
    four = _tiny(FOUR)
    out = {"sound": _run(four, seed), "one_chip": _run(_tiny(ONE), seed)}
    for name, make in faults.SHARDED.items():
        with faults.planted(make(four.config)):
            out[name] = _run(four, seed)
    return out


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_SIM_CHUNK="32",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    p = subprocess.run([sys.executable, __file__, str(SEED)], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.splitlines()[-1])


def test_four_chip_cell_shards_its_sweeps_and_is_correct(runs):
    r = runs["sound"]
    assert r["correct"], r["checks"]
    assert r["checks"]["cells_differing_from_one_device"]["value"] == 0
    assert r["device"]["count"] == 4
    # Set-up and window sweeps on the four devices; the check on one, a
    # device's share of the first window sweep at a time.
    assert r["meshes"][-4:] == [None] * 4
    assert r["sizes"][-4:] == [CELLS // 4] * 4
    assert set(r["meshes"][:-4]) == {4} and len(r["meshes"]) >= 6
    assert set(r["sizes"][:-4]) == {CELLS}


@pytest.mark.parametrize("fault", ["fault_shards_swapped",
                                   "fault_exchange_left_out"])
def test_four_chip_fault_is_not_correct(runs, fault):
    r = runs[fault]
    assert not r["correct"], r["checks"]
    if fault == "fault_shards_swapped":
        # Every other check passes: only the one-device check sees it.
        failed = {k for k, c in r["checks"].items() if c["value"] > c["limit"]}
        assert failed == {"cells_differing_from_one_device"}
        assert r["checks"]["cells_differing_from_one_device"]["value"] \
            == CELLS // 2


def test_one_chip_cell_runs_on_one_device_among_four(runs):
    r = runs["one_chip"]
    assert r["correct"], r["checks"]
    assert set(r["meshes"]) == {None}
    assert "cells_differing_from_one_device" not in r["checks"]


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    print(json.dumps(child(int(sys.argv[1]))), flush=True)
