"""Gigabytes written per save: the bytes of every file the checkpoint store
writes (counter ``ckpt.bytes_written``: shards, manifest and marker;
replica copies are not counted) per ``AsyncCheckpointer.save`` (counter
``ckpt.saves``), over the whole run.  Divided into ``ckpt_serialize_s``
and ``ckpt_fsync_s`` it gives the write's bandwidth."""
from bench.program_spans import per_call


def read(ctx):
    got = per_call("ckpt.bytes_written", "ckpt.saves")
    return None if got is None else got / 1e9
