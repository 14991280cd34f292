"""Device milliseconds per run of the train step's program in the trace."""


def read(ctx):
    secs, runs = ctx["trace"].module_seconds(r"train_step")
    return 1e3 * secs / runs if runs else None
