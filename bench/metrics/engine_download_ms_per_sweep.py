"""Host milliseconds per sweep spent reading the final state back from the
device: the ``sim.download`` span of each ``sim.run_cells`` call that
started in the window, averaged."""
from bench.program_spans import per_request


def read(ctx):
    secs = per_request(ctx, "sim.run_cells", "sim.download")
    return None if secs is None else 1e3 * secs
