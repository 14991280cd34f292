"""Mean seconds per save spent writing file bodies: the ``ckpt.serialize``
spans (``np.savez`` of each shard, the manifest, the commit marker) summed
per save, over the saves that started in the window."""
from bench.program_spans import per_request


def read(ctx):
    return per_request(ctx, "ckpt.save", "ckpt.serialize")
