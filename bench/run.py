#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as one JSON line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are looked up by name in
``BENCHMARK.json`` (``bench/registry.py``); the configuration's ``system``
names the code in ``bench/systems/`` that sets the system up, drives it for
``--seconds`` and checks what it produced.  ``setup_s`` runs from the start
of this process to the start of the window.  With ``--trace 1`` the window
runs under the profiler and the cell's per-layer metrics are printed
instead of its end-to-end metrics.

The run needs TPU chips, as many as the cell asks for: without them it
exits 1 and prints no result.  The last lines on standard error are the
numbers compared, each beside its limit; the last line on standard output
is the result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path


def _process_age() -> float:
    """Seconds since this process started (from /proc where it exists)."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.clock_gettime(time.CLOCK_BOOTTIME) - start
    except (OSError, ValueError, IndexError):
        return 0.0


_T0 = time.monotonic() - _process_age()   # the process's start, monotonic
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
# libtpu would otherwise log under a fixed path in /tmp.
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    from bench import device, registry

    cell = registry.resolve(args.workload)
    try:
        dev = device.require_chips(cell.chips)
    except device.NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 1

    from bench.harness import run_cell
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()    # .jax_cache/ in the checkout, unless placed
    result, checks = run_cell(cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), t_process=_T0, dev=dev)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"({'ok' if c['ok'] else 'FAILED'})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
