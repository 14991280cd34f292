"""Mean seconds per save spent hashing the state for the manifest: the
``ckpt.hash`` span (SHA-256 of every leaf, read in place, on a pool of
threads) on the checkpointer's writer thread, over the saves that started
in the window."""
from bench.program_spans import per_request


def read(ctx):
    return per_request(ctx, "ckpt.save", "ckpt.hash")
