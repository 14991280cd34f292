"""Device microseconds per cell completed: busy seconds summed over the
devices in the traced window / cells completed by the sweeps in it."""


def read(ctx):
    t = ctx["trace"]
    lo, hi = ctx["traced_window"]
    cells = sum(done for t0, t1, done in ctx["sweeps_log"]
                if t0 >= lo and t1 <= hi)
    if not cells or not t.n_devices:
        return None
    return 1e6 * t.busy_s * t.n_devices / cells
