"""Mean seconds a save blocked the step loop for its device-to-host copy
(``AsyncCheckpointer.last_blocking_seconds``), over saves in the window."""


def read(ctx):
    vals = ctx.get("snapshot_s") or []
    return sum(vals) / len(vals) if vals else None
