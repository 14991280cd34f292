"""Checkpoint store + async checkpointer: atomicity, integrity, replication."""
import hashlib
import json
import os
import shutil
import time

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro.ckpt import (
    AsyncCheckpointer,
    latest_checkpoint,
    list_checkpoints,
    load_pytree,
    save_pytree,
    store,
)
from repro.ckpt.store import CorruptCheckpoint


@pytest.fixture()
def tree():
    k = jax.random.key(0)
    return {
        "params": {"w": jax.random.normal(k, (32, 16)),
                   "b": jnp.zeros((16,), jnp.bfloat16)},
        "opt": {"m": jnp.ones((32, 16)), "step": jnp.int32(7)},
    }


def test_save_load_roundtrip(tmp_path, tree):
    path = save_pytree(str(tmp_path), 5, tree, n_shards=3)
    assert os.path.basename(path) == "step_00000005"
    out = load_pytree(path, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_and_list(tmp_path, tree):
    for s in (1, 3, 2):
        save_pytree(str(tmp_path), s, tree)
    assert [s for s, _ in list_checkpoints(str(tmp_path))] == [1, 2, 3]
    step, _ = latest_checkpoint(str(tmp_path))
    assert step == 3


def test_uncommitted_checkpoint_ignored(tmp_path, tree):
    path = save_pytree(str(tmp_path), 1, tree)
    os.remove(os.path.join(path, "COMMITTED"))
    assert list_checkpoints(str(tmp_path)) == []
    with pytest.raises(FileNotFoundError):
        load_pytree(path, tree)


def test_corruption_detected(tmp_path, tree):
    path = save_pytree(str(tmp_path), 1, tree, n_shards=1)
    shard = os.path.join(path, "shard_0.npz")
    # corrupt one array in place
    data = dict(np.load(shard))
    key = sorted(data)[0]
    data[key] = data[key] + 1.0 if data[key].dtype.kind == "f" else data[key] + 1
    np.savez(shard, **data)
    with pytest.raises((IOError, ValueError)):
        load_pytree(path, tree, verify=True)


def test_shape_mismatch_rejected(tmp_path, tree):
    path = save_pytree(str(tmp_path), 1, tree)
    bad = dict(tree)
    bad["params"] = {"w": jnp.zeros((8, 8)), "b": tree["params"]["b"]}
    with pytest.raises(ValueError):
        load_pytree(path, bad)


def test_async_checkpointer_overlap_and_restore(tmp_path, tree):
    primary = str(tmp_path / "primary")
    ck = AsyncCheckpointer(primary, n_shards=2)
    blocking = ck.save(1, tree)
    assert blocking < 5.0  # snapshot cost only, not serialization
    ck.save(2, jax.tree.map(lambda x: x * 2, tree))
    ck.wait()
    step, out = ck.restore_latest(tree)
    assert step == 2
    np.testing.assert_allclose(np.asarray(out["opt"]["m"]),
                               2 * np.ones((32, 16)), rtol=1e-6)
    ck.close()


def test_replication_and_fallback(tmp_path, tree):
    primary = str(tmp_path / "primary")
    replicas = [str(tmp_path / f"rep{i}") for i in range(2)]
    ck = AsyncCheckpointer(primary, replicas=replicas, n_shards=2)
    ck.save(4, tree)
    ck.wait()
    for r in replicas:  # neighbour copies exist
        assert latest_checkpoint(r) is not None
    # destroy the primary: restore must fall back to a replica
    shutil.rmtree(primary)
    os.makedirs(primary)
    step, out = ck.restore_latest(tree)
    assert step == 4
    ck.close()


def test_restore_falls_back_when_primary_corrupt(tmp_path, tree):
    """The documented fallback path: a CORRUPT (not just missing) primary
    must be skipped and the restore served from a replica directory."""
    primary = str(tmp_path / "primary")
    replicas = [str(tmp_path / "rep0")]
    ck = AsyncCheckpointer(primary, replicas=replicas, n_shards=2)
    ck.save(3, tree)
    ck.wait()
    # Corrupt every shard of the primary in place, leaving COMMITTED intact
    # so listing still sees it — load must fail, then fall through.
    _, path = latest_checkpoint(primary)
    for name in os.listdir(path):
        if name.startswith("shard_"):
            with open(os.path.join(path, name), "wb") as f:
                f.write(b"not a checkpoint shard")
    step, out = ck.restore_latest(tree)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(out["opt"]["step"]), 7)
    ck.close()


def test_restore_into_wrongly_shaped_like_raises(tmp_path, tree):
    """Only damaged or missing copies are skipped: a ``like`` of other
    shapes is the caller's error and must surface, not read as "no
    checkpoint" (the trainer would then carry on from memory)."""
    ck = AsyncCheckpointer(str(tmp_path / "p"), replicas=[str(tmp_path / "r")],
                           n_shards=2)
    ck.save(3, tree)
    ck.wait()
    bad = dict(tree)
    bad["params"] = {"w": jnp.zeros((8, 8)), "b": tree["params"]["b"]}
    with pytest.raises(ValueError, match="checkpoint shape"):
        ck.restore_latest(bad)
    assert ck.last_restored is None
    step, _ = ck.restore_latest(tree)
    assert ck.last_restored == (3, os.path.join(str(tmp_path / "p"),
                                                "step_00000003"))
    ck.close()


def test_truncated_primary_falls_through_to_replica(tmp_path, tree):
    """Torn-write hardening: a TRUNCATED shard (partial write, not garbage)
    must fail the load — bad zip or integrity hash — and restore must fall
    through to a surviving replica."""
    primary = str(tmp_path / "primary")
    replicas = [str(tmp_path / "rep0")]
    ck = AsyncCheckpointer(primary, replicas=replicas, n_shards=2)
    ck.save(5, tree)
    ck.wait()
    _, path = latest_checkpoint(primary)
    for name in sorted(os.listdir(path)):
        if name.startswith("shard_"):
            shard = os.path.join(path, name)
            with open(shard, "r+b") as f:
                f.truncate(os.path.getsize(shard) // 2)
            break
    with pytest.raises(Exception):
        load_pytree(path, tree)
    step, out = ck.restore_latest(tree)
    assert step == 5
    np.testing.assert_array_equal(np.asarray(out["opt"]["step"]), 7)
    ck.close()


def test_no_part_files_survive_a_save(tmp_path, tree):
    """Every file inside a committed image is written via .part + rename;
    none of the intermediates may leak into the final directory."""
    path = save_pytree(str(tmp_path), 1, tree, n_shards=3)
    assert not [n for n in os.listdir(path) if n.endswith(".part")]
    assert sorted(n for n in os.listdir(path)) == \
        ["COMMITTED", "manifest.json", "shard_0.npz", "shard_1.npz",
         "shard_2.npz"]


def test_replica_tmp_dirs_invisible_to_listing(tmp_path, tree):
    """A crash mid-replication leaves only a ``.tmp`` sibling, which
    list_checkpoints must skip (it would otherwise look committed, since
    the COMMITTED marker is copied with the tree)."""
    ck = AsyncCheckpointer(str(tmp_path / "p"), n_shards=1)
    ck.save(1, tree)
    ck.wait()
    rep = str(tmp_path / "rep0")
    os.makedirs(rep)
    _, path = latest_checkpoint(str(tmp_path / "p"))
    shutil.copytree(path, os.path.join(rep, "step_00000001.tmp"))
    assert list_checkpoints(rep) == []
    ck.close()


def test_replication_factor_places_on_hrw_chosen_neighbours(tmp_path, tree):
    """R-way placement: each step's image lands on exactly the R replica
    dirs the rendezvous hash picks — deterministic, so restore (and any
    other host) can recompute the holder set."""
    from repro.p2p import rendezvous_placement

    replicas = [str(tmp_path / f"rep{i}") for i in range(4)]
    ck = AsyncCheckpointer(str(tmp_path / "primary"), replicas=replicas,
                           replication_factor=2, n_shards=1)
    for step in (1, 2):
        ck.save(step, tree)
    ck.wait()
    for step in (1, 2):
        chosen = rendezvous_placement(f"step_{step}", replicas, 2)
        for r in replicas:
            holds = any(s == step for s, _ in list_checkpoints(r))
            assert holds == (r in chosen), (step, r)
    # Fallback still works with the primary gone entirely.
    shutil.rmtree(str(tmp_path / "primary"))
    os.makedirs(str(tmp_path / "primary"))
    step, _ = ck.restore_latest(tree)
    assert step == 2
    ck.close()


def test_gc_keeps_newest(tmp_path, tree):
    ck = AsyncCheckpointer(str(tmp_path / "p"), n_shards=1)
    for s in range(6):
        ck.save(s, tree)
    ck.wait()
    ck.gc(keep=2)
    steps = [s for s, _ in list_checkpoints(str(tmp_path / "p"))]
    assert steps == [4, 5]
    ck.close()


def test_blocking_time_much_smaller_than_write(tmp_path):
    """The V the controller sees (blocking) must be << the full write —
    that's the async overlap the paper's V-term benefits from."""
    big = {"w": jnp.ones((512, 512, 8), jnp.float32)}
    ck = AsyncCheckpointer(str(tmp_path / "p"), n_shards=1)
    blocking = ck.save(1, big)
    ck.wait()
    assert ck.last_write_seconds > 0
    assert blocking <= max(ck.last_write_seconds, 0.05) * 5  # overlapped
    ck.close()


# ------------------------------------------------------ the manifest's hash
def _old_hash(a):
    return hashlib.sha256(a.tobytes()).hexdigest()[:16]


def _big_tree():
    """Over 16 MiB, with more leaves than hashing threads."""
    rng = np.random.default_rng(0)
    n_small = store._cpus() + 3
    return {
        "big": [rng.standard_normal(3 << 19, dtype=np.float32)
                for _ in range(3)],
        "small": [rng.standard_normal((i + 1, 7), dtype=np.float32)
                  for i in range(n_small)],
        "bf16": rng.standard_normal((64, 33)).astype(ml_dtypes.bfloat16),
        "step": np.int32(7),
    }


_base = np.arange(24, dtype=np.float32).reshape(4, 6)


@pytest.mark.parametrize("a", [
    _base,
    _base.astype(ml_dtypes.bfloat16),
    np.array(7, dtype=np.int32),
    _base.T,
    np.asfortranarray(_base),
    np.zeros((0, 3), np.float32),
], ids=["float32", "bfloat16", "0d-int32", "transposed", "fortran", "empty"])
def test_hash_equals_the_digest_of_tobytes(a):
    assert store._hash(a) == _old_hash(a)


def test_parallel_hash_writes_the_serial_manifest(tmp_path):
    tree = _big_tree()
    assert sum(a.nbytes for a in jax.tree.leaves(tree)) > 16 << 20
    path = save_pytree(str(tmp_path), 3, tree, n_shards=3)
    with open(os.path.join(path, "manifest.json"), "rb") as f:
        written = f.read()
    saved = json.loads(written)["leaves"]
    # The manifest the serial ``sha256(tobytes())`` loop wrote, leaves in
    # flattening order.
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    names = ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)
             for p, _ in flat]
    serial = {"step": 3, "n_shards": 3, "leaves": {
        name: {**saved[name], "sha256_16": _old_hash(np.asarray(leaf))}
        for name, (_, leaf) in zip(names, flat)}}
    assert written == json.dumps(serial).encode()

    out = load_pytree(path, tree, verify=True)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    shard = max((os.path.join(path, f) for f in os.listdir(path)
                 if f.endswith(".npz")), key=os.path.getsize)
    with open(shard, "r+b") as f:
        f.seek(os.path.getsize(shard) // 2)
        byte = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([byte[0] ^ 0xFF]))
    with pytest.raises(CorruptCheckpoint):
        load_pytree(path, tree, verify=True)


@pytest.mark.parametrize("big", [False, True], ids=["small", "large"])
def test_a_hash_error_reaches_the_caller_and_commits_nothing(
        tmp_path, tree, monkeypatch, big):
    if big:
        tree = _big_tree()
    victim = min(jax.tree.leaves(tree), key=lambda a: np.asarray(a).size)
    real = store._hash

    def failing(a):
        if a.shape == np.shape(victim) and a.dtype == np.asarray(victim).dtype:
            raise RuntimeError("hash failed")
        return real(a)

    monkeypatch.setattr(store, "_hash", failing)
    with pytest.raises(RuntimeError, match="hash failed"):
        save_pytree(str(tmp_path), 1, tree)
    assert list_checkpoints(str(tmp_path)) == []
    assert not os.path.exists(os.path.join(str(tmp_path), "step_00000001"))
