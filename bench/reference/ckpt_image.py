"""Fingerprints of a saved state, on the chip and read back from disk.

A leaf's fingerprint is two 32-bit words over its raw bytes, taken as
unsigned integers of the leaf's item size: their sum and their sum
weighted by ``(index mod 65521) + 1``, both modulo 2**32.  A changed,
lost or moved value changes it.  The chip computes it for the state the
trainer saved, before the next step consumes that state, and again for
the committed image that the plain reader below reads back from disk,
parsing the image's layout (``step_<N>/manifest.json`` naming each leaf's
``shard_<i>.npz`` and key, in the state's flattening order) with nothing
but json and numpy.  Stored bfloat16 leaves come back as raw 2-byte words, read
as uint16: the same bytes.
"""
from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

MOD = 65521
_UINT = {1: np.uint8, 2: np.uint16, 4: np.uint32}


def _device_leaf(x):
    size = x.dtype.itemsize
    words = jax.lax.bitcast_convert_type(
        x.reshape(-1), {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[size])
    words = words.astype(jnp.uint32)
    idx = (jnp.arange(words.shape[0], dtype=jnp.uint32) % MOD) + 1
    return jnp.stack([jnp.sum(words), jnp.sum(words * idx)])


@jax.jit
def _fingerprint_all(leaves):
    return jnp.stack([_device_leaf(x) for x in leaves])


def device_fingerprints(tree) -> Any:
    """(n_leaves, 2) uint32 on the chip, in the tree's flattening order."""
    return _fingerprint_all(jax.tree.leaves(tree))


def read_image(step_dir: Path) -> List[np.ndarray]:
    """The leaves of one committed image, in the manifest's order."""
    if not (step_dir / "COMMITTED").exists():
        raise FileNotFoundError(f"{step_dir} is not committed")
    with open(step_dir / "manifest.json") as f:
        manifest = json.load(f)
    shards: Dict[int, Any] = {}
    out = []
    try:
        for meta in manifest["leaves"].values():
            s = int(meta["shard"])
            if s not in shards:
                shards[s] = np.load(step_dir / f"shard_{s}.npz")
            arr = shards[s][meta["key"]]
            if list(arr.shape) != list(meta["shape"]):
                raise ValueError(f"{step_dir}: leaf {meta['key']} has shape "
                                 f"{arr.shape}, manifest says {meta['shape']}")
            if arr.dtype.kind == "V":   # bfloat16 comes back as raw bytes
                arr = arr.view(_UINT[arr.dtype.itemsize])
            out.append(arr)
    finally:
        for z in shards.values():
            z.close()
    return out


def leaves_differing(root: os.PathLike, fingerprints: Dict[int, Any]) -> int:
    """Leaves, over every saved step, whose image on disk differs from the
    state that was saved; a missing image counts all of its leaves."""
    bad = 0
    for step, fp in fingerprints.items():
        want = np.asarray(fp)
        try:
            leaves = read_image(Path(root) / f"step_{step:08d}")
        except (OSError, ValueError, KeyError):
            bad += want.shape[0]
            continue
        if len(leaves) != want.shape[0]:
            bad += want.shape[0]
            continue
        got = np.asarray(device_fingerprints(leaves))
        bad += int(np.sum(np.any(got != want, axis=1)))
    return bad
