"""Share of the traced window in which no program ran on the device, in
percent, averaged over the devices in the trace."""


def read(ctx):
    share = ctx["trace"].idle_share
    return None if share is None else 100.0 * share
