"""Cell batches for the Monte-Carlo engine, built from data files.

``engine_cells(config, traffic, seeds)`` reads a configuration file
(``bench/configs/engine-*.json``: peers per job, slots in the network,
peer MTBF, work, V, T_d, policy) and a traffic file (skewed work, if any) and returns one
``CellSpec`` per seed.  ``million_peer_cells`` and ``pm_cells`` are the
benchmark's own copies of the fleet grids of ``benchmarks/fleet.py``
(``million_peer_cells`` and ``_pm_cells``), so that an edit there does not
move the yardstick.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

SEED_SPACE = 2 ** 31   # the engine keys each cell from a uint32 seed


def engine_cells(config: Dict[str, Any], traffic: Dict[str, Any],
                 seeds: Sequence[int]) -> List[Any]:
    """One cell per seed.  ``traffic["skew_share"]`` of the cells (the
    first ones) get ``traffic["skew_work_factor"]`` times the work."""
    from repro.sim import CellSpec, PolicyConfig, scenario

    k = int(config["peers_per_job"])
    v, t_d = float(config["checkpoint_s"]), float(config["restore_s"])
    p = config["policy"]
    scen = scenario("constant", mtbf=float(config["peer_mtbf_s"]))
    pol = PolicyConfig(kind=p["kind"], prior_mu=1.0 / float(p["prior_peer_mtbf_s"]),
                       prior_v=v, regime=p["regime"],
                       gossip_period=float(p["gossip_period_s"]),
                       gossip_fanout=int(p["gossip_fanout"]))
    work = float(config["work_s"])
    n_skew = round(len(seeds) * float(traffic.get("skew_share", 0.0)))
    factor = float(traffic.get("skew_work_factor", 1.0))
    return [CellSpec(scenario=scen, policy=pol, seed=int(s), k=k,
                     n_slots=int(config["slots"]),
                     work=work * factor if i < n_skew else work, V=v, T_d=t_d)
            for i, s in enumerate(seeds)]


def sweep_seeds(base: int, sweep: int, n: int) -> List[int]:
    """Seeds of sweep ``sweep``: fresh for every sweep, all below 2**31."""
    start = base + sweep * n
    return [(start + i) % SEED_SPACE for i in range(n)]


def million_peer_cells(n: int, k: int = 1_000_000, seed0: int = 0):
    """``n`` class-pooled gossip cells of k-peer jobs (per-job MTBF 250 s)."""
    config = {"peers_per_job": k, "slots": 4 * k,
              "peer_mtbf_s": 250.0 * k, "work_s": 1800.0,
              "checkpoint_s": 20.0, "restore_s": 50.0,
              "policy": {"kind": "adaptive", "regime": "gossip",
                         "prior_peer_mtbf_s": 250.0 * k,
                         "gossip_period_s": 600.0, "gossip_fanout": 2}}
    return engine_cells(config, {}, range(seed0, seed0 + n))


def pm_cells(n: int, *, k: int = 64, work: float = 4 * 3600.0,
             skew: int = 0, seed0: int = 0):
    """``n`` class-pooled gossip cells of 64-peer jobs; the first ``skew``
    cells carry 8x the work (a straggler block)."""
    mtbf = 4000.0
    config = {"peers_per_job": k, "slots": 4 * k, "peer_mtbf_s": mtbf,
              "work_s": work, "checkpoint_s": 20.0, "restore_s": 50.0,
              "policy": {"kind": "adaptive", "regime": "gossip",
                         "prior_peer_mtbf_s": 8.0 * mtbf,
                         "gossip_period_s": 600.0, "gossip_fanout": 2}}
    traffic = {"skew_share": skew / n if n else 0.0, "skew_work_factor": 8.0}
    return engine_cells(config, traffic, range(seed0, seed0 + n))
