"""Blocking host round trips per sweep: the early-exit checks after each
chunk (counter ``sim.host_syncs``) per ``run_cells`` call (counter
``sim.run_cells``), over the whole run.  Times the per-sync cost in the
trace (``repro.sim.sync``) gives what a sweep loses to them."""
from bench.program_spans import per_call


def read(ctx):
    return per_call("sim.host_syncs", "sim.run_cells")
