"""Plain reference of one job under periodic checkpointing and churn.

The model of the paper (arXiv:0711.3949) with a known failure rate: the
job's peers fail as one Poisson process of rate ``rate``; work runs in
segments of the Eq. 11 interval, each followed by a checkpoint of ``V``
seconds (none after the last); a failure loses the segment in progress and
is followed by restore attempts of ``T_d`` seconds, each of which a
failure can cut short.  Every cell accumulates wall, checkpoint, restore
and wasted seconds in ``dtype``, so wall = work + checkpoint + restore +
waste holds to that type's rounding: float64 as the configuration states,
float32 for the control.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def lambert_w0(x: float) -> float:
    """Principal branch of Lambert W on [-1/e, 0), by Newton's method."""
    w = -0.5
    for _ in range(100):
        e = np.exp(w)
        step = (w * e - x) / (e * (w + 1.0))
        w -= step
        if abs(step) < 1e-15:
            break
    return float(w)


def interval(rate: float, v: float) -> float:
    """The work per segment that minimizes expected time per unit of work,
    (exp(rate (T + V)) - 1) / (rate T): T = (1 + W0(-exp(-rate V - 1)))/rate."""
    return (1.0 + lambert_w0(-np.exp(-rate * v - 1.0))) / rate


def simulate(n: int, seed: int, *, work: float, v: float, t_d: float,
             rate: float, dtype=np.float64) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    dt = np.dtype(dtype)
    seg, cv, ct = dt.type(interval(rate, v)), dt.type(v), dt.type(t_d)
    w = dt.type(work)
    done = np.zeros(n, dt)
    acc = {k: np.zeros(n, dt) for k in ("wall", "ckpt", "restore", "waste")}
    n_ckpt = np.zeros(n, np.int64)
    n_fail = np.zeros(n, np.int64)
    active = np.ones(n, bool)
    while active.any():
        left = w - done
        last = left <= seg
        length = np.minimum(seg, left)
        need = length + np.where(last, dt.type(0), cv)
        f = rng.exponential(1.0 / rate, n).astype(dt)
        ok = active & (f >= need)
        fail = active & ~ok
        acc["wall"] += np.where(ok, need, np.where(fail, f, dt.type(0)))
        acc["ckpt"] += np.where(ok & ~last, cv, dt.type(0))
        acc["waste"] += np.where(fail, f, dt.type(0))
        done += np.where(ok, length, dt.type(0))
        n_ckpt += ok & ~last
        n_fail += fail
        pending = fail.copy()
        while pending.any():
            g = rng.exponential(1.0 / rate, n).astype(dt)
            took = np.where(g >= ct, ct, g)
            acc["restore"] += np.where(pending, took, dt.type(0))
            acc["wall"] += np.where(pending, took, dt.type(0))
            pending &= g < ct
        active = done < w
    return dict(acc, work=np.full(n, w, dt), n_checkpoints=n_ckpt,
                n_failures=n_fail)


def accounting_error(r: Dict[str, np.ndarray]) -> float:
    """Largest |wall - (work + ckpt + restore + waste)| / wall, in float64."""
    parts = (r["work"].astype(np.float64) + r["ckpt"].astype(np.float64)
             + r["restore"].astype(np.float64) + r["waste"].astype(np.float64))
    wall = r["wall"].astype(np.float64)
    return float(np.max(np.abs(wall - parts) / wall))
