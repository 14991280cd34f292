"""Asynchronous checkpoint writer with neighbour replication.

The paper's V (checkpoint overhead) has two parts: capturing the state and
pushing it to reliable storage.  On the training loop we minimize the
*blocking* part: the step only pays for the host-side snapshot
(device_get); serialization + fsync + replication run on a background
thread, overlapped with subsequent steps.  The measured blocking time is
reported to the adaptive controller as V — exactly the quantity the paper's
Eq. 2 probe estimates, but measured directly (DESIGN.md Sec 2).

Replication: each checkpoint is copied to 'neighbour' stores (distinct
directories standing in for other hosts' disks / other cells' filestores),
the analogue of the paper's P2P distributed storage.  Placement follows
the overlay's rule (:func:`repro.p2p.rendezvous_placement`): when
``replication_factor`` R is set, each step's image lands on the R
neighbours that win the deterministic highest-random-weight hash for that
step — every host computes the same holder set with no coordination, and
successive steps spread load across the neighbourhood.  ``None`` keeps
the legacy copy-to-all behaviour.  Restore falls back through replicas
when the primary is corrupt or missing.
"""
from __future__ import annotations

import os
import queue
import shutil
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np

from repro import tracing
from repro.ckpt import store
from repro.p2p.overlay import rendezvous_placement

Params = Any


@dataclass
class AsyncCheckpointer:
    root: str
    replicas: Sequence[str] = ()
    n_shards: int = 4
    replication_factor: Optional[int] = None  # R neighbours per step (HRW)
    _q: queue.Queue = field(default_factory=lambda: queue.Queue(maxsize=2), repr=False)
    _thread: Optional[threading.Thread] = field(default=None, repr=False)
    _exc: Optional[BaseException] = field(default=None, repr=False)
    _pending: int = field(default=0, repr=False)
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)
    last_blocking_seconds: float = field(default=0.0, repr=False)
    last_write_seconds: float = field(default=0.0, repr=False)
    last_restored: Optional[Tuple[int, str]] = field(default=None, repr=False)
    last_restore_seconds: float = field(default=0.0, repr=False)

    def __post_init__(self):
        os.makedirs(self.root, exist_ok=True)
        for r in self.replicas:
            os.makedirs(r, exist_ok=True)
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    # ------------------------------------------------------------------ #
    def _worker(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            step, snapshot, cause = item
            try:
                with tracing.span("ckpt.write", id=step, parent=cause) as sp:
                    path = store.save_pytree(self.root, step, snapshot,
                                             self.n_shards)
                    for r in self._placement(step):
                        with tracing.span("ckpt.replicate"):
                            self._replicate(path, r)
                self.last_write_seconds = sp.seconds
            except BaseException as e:
                self._exc = e
            finally:
                with self._lock:
                    self._pending -= 1

    @staticmethod
    def _replicate(path: str, replica_root: str) -> None:
        """Copy one committed image into a replica directory, atomically:
        copy into a ``.tmp`` sibling (invisible to list_checkpoints) and
        rename into place, so a crash mid-copy never leaves a half-written
        replica that restore_latest could mistake for a committed image
        (its COMMITTED marker would already have been copied by a plain
        copytree)."""
        dst = os.path.join(replica_root, os.path.basename(path))
        tmp = dst + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        shutil.copytree(path, tmp)
        if os.path.exists(dst):
            shutil.rmtree(dst)
        os.rename(tmp, dst)

    def _placement(self, step: int) -> Sequence[str]:
        """Replica directories receiving this step's image."""
        if self.replication_factor is None:
            return self.replicas
        return rendezvous_placement(f"step_{step}", list(self.replicas),
                                    self.replication_factor)

    # ------------------------------------------------------------------ #
    def save(self, step: int, tree: Params) -> float:
        """Enqueue an async save.  Returns the BLOCKING seconds (the V the
        controller should see): host snapshot + any queue backpressure."""
        if self._exc is not None:
            exc, self._exc = self._exc, None
            raise exc
        with tracing.span("ckpt.save", id=step) as sp:
            # Snapshot to host memory so the device arrays can keep training.
            with tracing.span("ckpt.snapshot"):
                snapshot = jax.tree.map(lambda x: np.asarray(x), tree)
            with self._lock:
                self._pending += 1
            with tracing.span("ckpt.enqueue"):
                # blocks only when 2 saves are queued
                self._q.put((step, snapshot, sp))
        tracing.count("ckpt.saves")
        self.last_blocking_seconds = sp.seconds
        return sp.seconds

    def wait(self, timeout: Optional[float] = None) -> None:
        """Block until all queued saves have landed."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with tracing.span("ckpt.wait"):
            while True:
                with self._lock:
                    if self._pending == 0:
                        break
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError(
                        "async checkpoint writes did not finish")
                time.sleep(0.005)
        if self._exc is not None:
            exc, self._exc = self._exc, None
            raise exc

    def close(self):
        self.wait()
        self._q.put(None)
        self._thread.join(timeout=5)

    # ------------------------------------------------------------------ #
    def restore_latest(self, like: Params) -> Optional[tuple]:
        """(step, tree) from the newest checkpoint found anywhere.

        Candidates from the primary and every replica are tried newest
        first (ties prefer the primary): with R-way placement the newest
        image may live only on the HRW-chosen neighbours, and a corrupt or
        missing copy falls back to the next-newest surviving replica.  Any
        other error, such as a ``like`` of other shapes, propagates.  The
        (step, directory) read is kept in ``last_restored``, and the
        seconds the whole search and load took in ``last_restore_seconds``.
        """
        with tracing.span("ckpt.restore") as sp:
            got = self._load_newest(like)
        if got is None:
            return None
        step, path, tree = got
        self.last_restored = (step, path)
        self.last_restore_seconds = sp.seconds
        return step, tree

    def _load_newest(self, like: Params) -> Optional[tuple]:
        found = []
        for root in (self.root, *self.replicas):
            got = store.latest_checkpoint(root)
            if got is not None:
                found.append(got)
        for step, path in sorted(found, key=lambda sp: sp[0], reverse=True):
            try:
                return step, path, store.load_pytree(path, like)
            except (OSError, KeyError):
                continue  # corrupt or missing copy: try the next candidate
        return None

    def gc(self, keep: int = 3) -> None:
        """Drop all but the newest ``keep`` checkpoints everywhere."""
        for root in (self.root, *self.replicas):
            cks = store.list_checkpoints(root)
            for _, path in cks[:-keep] if keep else cks:
                shutil.rmtree(path, ignore_errors=True)
