"""Threads that hashed the state per save: the counter
``ckpt.hash_threads`` (one per leaf, up to the cores the writer may use)
per ``AsyncCheckpointer.save`` (counter ``ckpt.saves``), over the whole
run.  Read beside ``ckpt_hash_s``, it tells a host that
gave few threads from a slow hash.  None where the program does not count
its hashing threads."""
from bench import program_spans


def read(ctx):
    tracing = program_spans._tracing()
    if tracing is None or "ckpt.hash_threads" not in tracing.counters():
        return None
    return program_spans.per_call("ckpt.hash_threads", "ckpt.saves")
