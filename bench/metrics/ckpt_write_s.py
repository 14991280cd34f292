"""Mean seconds of a save's write to disk: serialize, hash, fsync, commit
(``AsyncCheckpointer.last_write_seconds``), over saves in the window."""


def read(ctx):
    vals = ctx.get("write_s") or []
    return sum(vals) / len(vals) if vals else None
