#!/usr/bin/env python3
"""Readings of the controls and faults that the correctness limits are set
between; not run by the benchmark's own runs.

    python3 bench/control.py --workload <name> --seeds 1,2,3

For a trainer cell: the float32 reference against itself computed with
float8 matrix products (the control), and against the reference with half
of each batch left out, the mean taken over the rest (a fault), at the
cell's own sizes, on each seed.  With ``--program``: the program's own
readings, from the cell's set-up and first timed step, as a run takes
them (on the chip).  For an engine cell: the readings of a run cut to its
set-up and one timed sweep, with each control and fault of
``bench/faults.py`` in the program's place (``--program``: the program's
own).  Each reading prints as one
JSON line.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def trainer_readings(cell, seed: int, n_steps: int):
    from bench import registry, seeds
    from bench.compare import max_rel_gap, worst_leaf_gap
    from bench.reference import olmo

    s32 = seeds.derive(seed, registry.system("trainer").SEED_TAG)
    data = cell.traffic["data"]
    ref = olmo.train(cell.config, data, s32, n_steps)
    half = int(cell.config["run"]["global_batch"]) // 2
    variants = {
        "control_fp8": olmo.train(cell.config, data, s32, n_steps,
                                  precision="fp8"),
        "fault_half_batch": olmo.train(cell.config, data, s32, n_steps,
                                       rows=range(half)),
    }
    for name, r in variants.items():
        yield {
            "reading": name, "seed": seed,
            "loss1_rel_gap": max_rel_gap(r.losses[:1], ref.losses[:1]),
            "loss_rel_gap_3_steps": max_rel_gap(r.losses, ref.losses),
            "grad_leaf_gap": worst_leaf_gap(r.grad_norms, ref.grad_norms),
            "change_leaf_gap": worst_leaf_gap(r.change_norms,
                                              ref.change_norms),
        }


def program_readings(cell, seed: int, reading: str = "program"):
    """The cell's set-up and its first timed unit of work as a run takes
    them, then its checks."""
    import time

    from bench import device, harness, registry

    window = harness.Window(seconds=0.0, t_process=time.monotonic(),
                            traced=False, compiles=harness.CompileCounter())
    out = registry.system(cell.config["system"]).run(
        cell, seed=seed, window=window, rec=harness.Recorder(),
        dev=device.require_chips(cell.chips))
    yield dict({"reading": reading, "seed": seed},
               **{k: c["value"] for k, c in out.checks.items()})


def engine_readings(cell, seed: int):
    from bench import faults

    for name, make in faults.ENGINE.items():
        with faults.planted(make(cell.config)):
            yield from program_readings(cell, seed, reading=name)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, e.g. 11,12,13")
    ap.add_argument("--program", action="store_true",
                    help="read the program's own numbers on these seeds")
    args = ap.parse_args(argv)
    from bench import registry

    cell = registry.resolve(args.workload)
    if args.program or cell.config["system"] == "engine":
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.program:
            rows = program_readings(cell, seed)
        elif cell.config["system"] == "engine":
            rows = engine_readings(cell, seed)
        else:
            rows = trainer_readings(cell, seed,
                                    int(cell.traffic["warm_steps"]))
        for row in rows:
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
