"""Mean seconds per save spent in fsync: the ``ckpt.fsync`` spans (every
file and directory sync of the image) summed per save, over the saves that
started in the window."""
from bench.program_spans import per_request


def read(ctx):
    return per_request(ctx, "ckpt.save", "ckpt.fsync")
