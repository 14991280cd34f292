"""Host milliseconds per sweep spent dispatching the packed cells and their
keys to the device and the ops that build the initial state: the
``sim.upload`` span of each ``sim.run_cells`` call that started in the
window, averaged.  The copies are asynchronous: the span ends once they are
issued, and the wait for them to land falls in the first ``sim.chunk`` or
``sim.sync``."""
from bench.program_spans import per_request


def read(ctx):
    secs = per_request(ctx, "sim.run_cells", "sim.upload")
    return None if secs is None else 1e3 * secs
