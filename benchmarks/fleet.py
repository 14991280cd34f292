"""Fleet-scale engine benchmarks: fused step kernel and cell sharding.

Two comparisons, both on class-pooled (pm) gossip batches — the form the
fleet-scale path exists for:

* ``step='scan'`` vs ``step='fused'``: the stock jitted ``lax.scan`` chunk
  body against the Pallas sim-step kernel (interpret mode on CPU; the
  derived column carries the speedup so the regression gate can hold the
  fused path to >= scan);
* single-device vs sharded: the same batch through ``mesh=None`` and
  ``mesh='auto'`` — on a one-device host both rows report n_devices=1 and
  near-identical times; CI runs this section under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` where the sharded
  row shows the multi-device scaling.

Plus the tentpole acceptance shape: a 1M-peer, class-pooled cell grid
(10k cells full / 512 fast) timed end to end.
"""
from __future__ import annotations

import time
from typing import List

import numpy as np

from repro.sim import CellSpec, PolicyConfig, run_cells, scenario

V, TD = 20.0, 50.0
MTBF = 4000.0
PRIOR_MU = 1.0 / (8.0 * MTBF)


def _pm_cells(B: int, *, k: int = 64, work: float = 4 * 3600.0,
              skew: int = 0):
    """B class-pooled gossip cells; ``skew`` > 0 gives the first ``skew``
    cells 8x work (a straggler block — the completion profile the fused
    kernel's early exit targets)."""
    scen = scenario("constant", mtbf=MTBF)
    pol = PolicyConfig(kind="adaptive", prior_mu=PRIOR_MU, prior_v=V,
                       regime="gossip", gossip_period=600.0, gossip_fanout=2)
    return [CellSpec(scenario=scen, policy=pol, seed=s, k=k, n_slots=4 * k,
                     work=(8 * work if s < skew else work), V=V, T_d=TD)
            for s in range(B)]


def _time(fn, reps: int = 3) -> float:
    fn()  # compile/warm
    t0 = time.monotonic()
    for _ in range(reps):
        fn()
    return (time.monotonic() - t0) / reps * 1e6  # us


def step_rows(fast: bool = False) -> List[str]:
    B = 64 if fast else 256
    cells = _pm_cells(B, work=1800.0, skew=max(B // 8, 1))
    t_scan = _time(lambda: run_cells(cells, backend="jax", mesh=None,
                                     step="scan"))
    t_fused = _time(lambda: run_cells(cells, backend="jax", mesh=None,
                                      step="fused"))
    rows = []
    for name, us in (("scan", t_scan), ("fused", t_fused)):
        cps = B / (us / 1e6)
        rows.append(f"fleet_step_{name}_B{B},{us:.0f},"
                    f"cells_per_s={cps:.1f};speedup_vs_scan="
                    f"{t_scan / us:.2f}x")
    return rows


def shard_rows(fast: bool = False) -> List[str]:
    import jax

    n_dev = len(jax.devices())
    B = (64 if fast else 256) * max(n_dev, 1)
    cells = _pm_cells(B)
    t_1 = _time(lambda: run_cells(cells, backend="jax", mesh=None), reps=2)
    t_n = _time(lambda: run_cells(cells, backend="jax", mesh="auto"), reps=2)
    rows = []
    for name, us, nd in (("1dev", t_1, 1), (f"{n_dev}dev", t_n, n_dev)):
        cps = B / (us / 1e6)
        rows.append(f"fleet_shard_{name}_B{B},{us:.0f},"
                    f"cells_per_s={cps:.1f};n_devices={nd};"
                    f"scaling_vs_1dev={t_1 / us:.2f}x")
    return rows


def million_peer_cells(B: int, k: int = 1_000_000) -> List[CellSpec]:
    """B class-pooled gossip cells of k-peer jobs (per-job MTBF 250 s)."""
    scen = scenario("constant", mtbf=250.0 * 1e6)
    pol = PolicyConfig(kind="adaptive", prior_mu=1.0 / (250.0 * 1e6),
                       prior_v=V, regime="gossip", gossip_period=600.0,
                       gossip_fanout=2)
    return [CellSpec(scenario=scen, policy=pol, seed=s, k=k, n_slots=4 * k,
                     work=1800.0, V=V, T_d=TD) for s in range(B)]


def million_peer_rows(fast: bool = False) -> List[str]:
    k = 1_000_000
    B = 512 if fast else 10_000
    cells = million_peer_cells(B, k)
    t0 = time.monotonic()
    res = run_cells(cells, backend="jax", mesh="auto")
    us = (time.monotonic() - t0) * 1e6
    assert bool(np.asarray(res.completed).all())
    import jax
    return [f"fleet_1M_peer_B{B},{us:.0f},"
            f"cells_per_s={B / (us / 1e6):.1f};"
            f"n_devices={len(jax.devices())};peers_per_cell={k}"]


def run_all(fast: bool = False) -> List[str]:
    rows = ["name,us_per_call,derived"]
    rows += step_rows(fast=fast)
    rows += shard_rows(fast=fast)
    rows += million_peer_rows(fast=fast)
    return rows
