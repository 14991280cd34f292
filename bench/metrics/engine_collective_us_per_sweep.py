"""Device microseconds per sweep in collectives between chips (all-reduce,
all-gather, collective-permute, all-to-all), averaged over the devices in
the trace, over the sweeps that ran whole in the traced window.  None
where the trace holds no such operation, as on one chip."""
COLLECTIVES = r"^(all-reduce|all-gather|collective-permute|all-to-all)"


def read(ctx):
    secs = ctx["trace"].op_seconds(COLLECTIVES)
    lo, hi = ctx["traced_window"]
    sweeps = sum(1 for t0, t1, _ in ctx["sweeps_log"] if t0 >= lo and t1 <= hi)
    if not secs or not sweeps:
        return None
    return 1e6 * secs / sweeps
