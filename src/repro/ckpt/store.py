"""Sharded checkpoint store: npz shards + JSON manifest + SHA256 integrity.

Layout of one checkpoint:

    <root>/step_<N>/
        manifest.json         # leaf paths, shapes, dtypes, shard map, hashes
        shard_<i>.npz         # leaf arrays (split by shard)
        COMMITTED             # atomic commit marker (written last)

Writes go to ``step_<N>.tmp`` and are renamed after the COMMITTED marker is
in place, so a crash mid-save never corrupts the latest checkpoint — the
paper's 'reliable storage' requirement.  Every file inside the tmp dir is
itself written atomically (``.part`` + fsync + ``os.replace``) and the
marker goes last, so a torn write can never masquerade as a committed
image: a truncated shard fails the load (bad zip / integrity hash) and the
restore path falls through to the next replica.  ``n_shards`` emulates
per-host sharding: leaves are assigned round-robin (by size) to shards,
matching a multi-host save where each host writes its own shard file.
Replication to 'neighbour' stores (the P2P storage analogue) lives in
async_ckpt.py.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import zipfile
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro import tracing

Params = Any

_MANIFEST = "manifest.json"
_COMMITTED = "COMMITTED"


class CorruptCheckpoint(IOError):
    """A committed copy whose files are damaged on disk (unreadable shard
    or manifest, manifest/shard disagreement, integrity-hash mismatch).
    Restore skips such a copy and tries the next one."""


def _leaf_paths(tree) -> List[Tuple[str, np.ndarray]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        out.append((name, np.asarray(leaf)))
    return out


def _hash(arr: np.ndarray) -> str:
    # The bytes of ``tobytes()`` (C order), read in place: no copy for a
    # C-contiguous array, and a byte view also covers ml_dtypes and 0-d.
    flat = np.ascontiguousarray(arr).reshape(-1).view(np.uint8)
    return hashlib.sha256(flat).hexdigest()[:16]


def _cpus() -> int:
    """The cores this process may run on (its affinity, where the OS has one)."""
    if hasattr(os, "sched_getaffinity"):
        return len(os.sched_getaffinity(0))
    return os.cpu_count() or 1


def _hash_leaves(arrs: List[np.ndarray]) -> List[str]:
    """``_hash`` of each array, in their order.

    Hashed on a thread pool of one thread per leaf, up to the cores this
    process may use (``hashlib`` releases the GIL for large buffers),
    largest leaves first since the largest sets the floor.  Counts the
    threads in ``ckpt.hash_threads``.
    """
    workers = max(1, min(len(arrs), _cpus()))
    tracing.count("ckpt.hash_threads", workers)
    by_size = sorted(range(len(arrs)), key=lambda i: -arrs[i].nbytes)
    with ThreadPoolExecutor(workers) as pool:
        done = {i: pool.submit(_hash, arrs[i]) for i in by_size}
        return [done[i].result() for i in range(len(arrs))]


def _atomic_write(path: str, writer) -> None:
    """Write a file via ``.part`` + fsync + rename so it is all-or-nothing.

    ``writer(fileobj)`` produces the content.  A crash before the
    ``os.replace`` leaves only a ``.part`` file that every reader ignores;
    a crash after it leaves the complete, durable file.
    """
    part = path + ".part"
    with open(part, "wb") as f:
        with tracing.span("ckpt.serialize"):
            writer(f)
            f.flush()
        with tracing.span("ckpt.fsync"):
            os.fsync(f.fileno())
        tracing.count("ckpt.bytes_written", f.tell())
    os.replace(part, path)


def _fsync_dir(path: str) -> None:
    """Best-effort directory fsync (durability of the rename itself)."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:  # pragma: no cover - platform without dir-open support
        return
    try:
        with tracing.span("ckpt.fsync"):
            os.fsync(fd)
    except OSError:  # pragma: no cover - filesystems that reject dir fsync
        pass
    finally:
        os.close(fd)


def save_pytree(root: str, step: int, tree: Params, n_shards: int = 4) -> str:
    """Atomically save a pytree checkpoint.  Returns the final directory."""
    final = os.path.join(root, f"step_{step:08d}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)

    leaves = _leaf_paths(tree)
    # Greedy size-balanced shard assignment (stable order for determinism).
    order = sorted(range(len(leaves)), key=lambda i: -leaves[i][1].nbytes)
    shard_of: Dict[str, int] = {}
    loads = [0] * max(n_shards, 1)
    for i in order:
        s = int(np.argmin(loads))
        shard_of[leaves[i][0]] = s
        loads[s] += leaves[i][1].nbytes

    manifest: Dict[str, Any] = {"step": step, "n_shards": n_shards, "leaves": {}}
    shards: Dict[int, Dict[str, np.ndarray]] = {}
    with tracing.span("ckpt.hash"):
        digests = _hash_leaves([arr for _, arr in leaves])
        for (name, arr), digest in zip(leaves, digests):
            s = shard_of[name]
            key = f"a{len(shards.setdefault(s, {}))}"
            # npz cannot store ml_dtypes (bfloat16/fp8): persist a same-width
            # integer view; the true dtype is recorded in the manifest.
            stored = arr
            if arr.dtype.name not in np.sctypeDict:
                stored = arr.view(np.dtype(f"u{arr.dtype.itemsize}"))
            shards[s][key] = stored
            manifest["leaves"][name] = {
                "shard": s, "key": key, "shape": list(arr.shape),
                "dtype": str(arr.dtype), "sha256_16": digest,
            }

    for s, arrs in shards.items():
        _atomic_write(os.path.join(tmp, f"shard_{s}.npz"),
                      lambda f, arrs=arrs: np.savez(f, **arrs))
    _atomic_write(os.path.join(tmp, _MANIFEST),
                  lambda f: f.write(json.dumps(manifest).encode()))
    with tracing.span("ckpt.commit"):
        # The marker is written (and fsynced) last: its presence certifies
        # that every shard above it is complete on disk.
        _atomic_write(os.path.join(tmp, _COMMITTED), lambda f: f.write(b"ok"))
        _fsync_dir(tmp)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
        _fsync_dir(root)
    return final


def is_committed(path: str) -> bool:
    return os.path.exists(os.path.join(path, _COMMITTED))


def load_pytree(path: str, like: Params, *, verify: bool = True) -> Params:
    """Load a checkpoint into the structure of ``like`` (shapes validated).

    A missing or damaged copy raises ``FileNotFoundError``,
    :class:`CorruptCheckpoint` or, for a leaf the manifest lacks,
    ``KeyError``; a ``like`` whose shapes differ from the checkpoint's
    raises ``ValueError``.
    """
    if not is_committed(path):
        raise FileNotFoundError(f"checkpoint at {path} is not committed")
    try:
        with open(os.path.join(path, _MANIFEST)) as f:
            manifest = json.load(f)
    except ValueError as e:
        raise CorruptCheckpoint(f"unreadable manifest in {path}") from e

    cache: Dict[int, Any] = {}

    def read(s: int, key: str) -> np.ndarray:
        try:
            if s not in cache:
                cache[s] = np.load(os.path.join(path, f"shard_{s}.npz"))
            return cache[s][key]
        except (ValueError, EOFError, zipfile.BadZipFile) as e:
            raise CorruptCheckpoint(f"unreadable shard {s} in {path}") from e

    flat, treedef = jax.tree_util.tree_flatten_with_path(like)
    out = []
    for pth, leaf in flat:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in pth)
        if name not in manifest["leaves"]:
            raise KeyError(f"leaf {name!r} missing from checkpoint {path}")
        meta = manifest["leaves"][name]
        arr = read(meta["shard"], meta["key"])
        if str(arr.dtype) != meta["dtype"]:
            # integer view of an ml_dtype (bfloat16/fp8): reinterpret
            import ml_dtypes  # noqa: F401  (registers the dtypes)
            arr = arr.view(np.dtype(meta["dtype"]))
        if list(arr.shape) != meta["shape"] or str(arr.dtype) != meta["dtype"]:
            raise CorruptCheckpoint(f"leaf {name!r}: manifest/shard mismatch")
        if tuple(arr.shape) != tuple(np.shape(leaf)):
            raise ValueError(
                f"leaf {name!r}: checkpoint shape {arr.shape} != expected {np.shape(leaf)}")
        if verify and _hash(arr) != meta["sha256_16"]:
            raise CorruptCheckpoint(
                f"leaf {name!r}: integrity hash mismatch (corrupt shard)")
        out.append(arr)
    return jax.tree_util.tree_unflatten(treedef, out)


def list_checkpoints(root: str) -> List[Tuple[int, str]]:
    """Committed checkpoints under root, sorted by step ascending."""
    if not os.path.isdir(root):
        return []
    out = []
    for d in os.listdir(root):
        if d.startswith("step_") and not d.endswith(".tmp"):
            p = os.path.join(root, d)
            if is_committed(p):
                try:
                    out.append((int(d[5:]), p))
                except ValueError:
                    continue
    return sorted(out)


def latest_checkpoint(root: str) -> Optional[Tuple[int, str]]:
    cks = list_checkpoints(root)
    return cks[-1] if cks else None
