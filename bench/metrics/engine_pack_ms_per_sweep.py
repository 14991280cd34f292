"""Host milliseconds per sweep spent packing cells into arrays
(``repro.sim.engine._pack``, timed from the benchmark), over the window."""


def read(ctx):
    packs = ctx["rec"].durations("engine.pack", *ctx["measured_window"])
    return 1e3 * sum(packs) / len(packs) if packs else None
