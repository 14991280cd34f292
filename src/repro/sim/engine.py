"""Batched cycle-level Monte-Carlo engine for checkpoint-policy simulation.

The per-event reference (:func:`repro.sim.job.simulate_job`) walks a Python
heap of individual peer deaths — exact, but serial and slow.  This engine
simulates at *checkpoint-cycle* granularity and is vectorized over a batch
of (seed x policy-config x scenario) cells:

* **JAX backend** — one ``lax.scan`` step per cycle with the whole cell
  batch as the carried state, jitted in float64, chunked so the host loop
  can exit as soon as every cell finishes.
* **NumPy backend** — the same step function driven by a Python loop over
  vectorized batch arrays; no compilation latency, eager-debuggable, and
  the double-precision reference the JAX path is tested against.  (The
  wider package imports jax at module scope, so this is a no-JIT path, not
  a no-JAX-install path.)

Model equivalence with the reference simulator (DESIGN.md Sec 3): the k job
peers have exponential lifetimes with hazard mu(t), so the job-level failure
process is Poisson with rate k*mu(t).  A cycle or restore attempt of length
L starting at t therefore survives with probability exp(-k mu L), and the
failure offset within a failed attempt is the exponential draw itself —
exactly the distribution the heap delivers, without materializing per-peer
events.

Two deliberate approximations (both switchable, both mean-preserving):

* The adaptive estimator's observation stream (deaths among the ``watch``
  neighbourhood) is fed in expectation — watch*mu*dt decayed through the
  same window-K MLE — instead of Poisson-sampled per step.  The windowed
  estimate tracks the true rate with the same lag as the paper's Eq. 1
  estimator but without sampling jitter.
* **Macro-stepping**: when a cycle's survival probability drops below
  ``macro_threshold``, the number of consecutive failures before the next
  success is sampled exactly (geometric), and the elapsed time of that
  whole failure burst — truncated-exponential attempt + geometric restore
  retries per failure — is drawn from a normal with the burst's exact mean
  and variance (CLT), capped by the scenario's hazard coherence time so
  time-varying rates are still honoured.  This turns livelocked /
  failure-dominated cells from tens of thousands of steps into tens.
  ``macro_threshold=0`` disables it for exact parity runs.  Adaptive
  cells cap each burst at ~one estimator-window turnover of watch deaths
  (``window/(watch*mu)`` seconds): the estimator only updates between
  steps, and an uncapped burst would outrun the adaptation that lets the
  exact path escape a mis-estimated livelock.

The adaptive policy mirrors :class:`AdaptiveCheckpointController`: a
windowed-MLE failure-rate estimate (exposure form, Gamma-prior smoothed),
exact V after the first checkpoint, T_d initialized to V until a restore is
seen, and the same interval clamps.

**Estimator regimes** (paper Sec 3.1.4, DESIGN.md Sec 3): the fidelity of
the adaptive estimator's information sharing is an explicit axis of every
cell, ``PolicyConfig.regime``:

* ``"pooled"`` — today's behaviour and the centralized upper bound: one
  estimator ingests the whole ``watch`` neighbourhood's observation
  stream in expectation, i.e. perfect, instantaneous sharing among the k
  peers.
* ``"isolated"`` — each of the k peers runs its own estimator fed only by
  its 1/k share of the watch neighbourhood, Poisson-sampled (estimator
  noise is exactly what distinguishes fidelity, so the expected-value
  shortcut does not apply); estimates are never exchanged.  The job's
  checkpoint decisions come from peer 0, the *decision peer*.
* ``"gossip(period, fanout, weight)"`` — isolated peers that every
  ``period`` seconds pull the mu estimates of ``fanout`` ring
  neighbours (a deterministic cyclic schedule — a circulant, doubly
  stochastic mixing matrix, so the peer average is preserved while the
  spread contracts) and blend them with ``ingest_gossip`` semantics:
  merged = (1-w)*local + w*remote_mean, after which the local window is
  re-seeded at the merged value (mirroring
  ``AdaptiveCheckpointController.ingest_gossip``).

Non-pooled regimes carry their estimator state in one of two *forms*:

* **per-peer** (``k <= _PEER_CAP``) — ``ema_d``/``ema_T``/``mu0``/``td_obs``
  carry a trailing peer axis sized ``_PEER_CAP`` whenever any cell in the
  batch runs this form (1 otherwise); per-peer observation noise comes
  from a dedicated stream per seed so a cell's realization never depends
  on batch composition.  This is the exact reference — and the parity
  oracle for:
* **class-pooled** (any ``k``; automatic above ``_PEER_CAP``, forceable
  via ``run_cells(peer_form=...)``) — the fleet-scale form (DESIGN.md
  Sec 9).  Only the *decision peer* (slot 0) keeps a sampled estimator
  row; the other ``k-1`` peers are exchangeable within their peer class
  and are carried as per-class sufficient-statistic moments
  (``pm_d``/``pm_T``/``pm_mu0``, width ``_CLS_CAP``) evolved in
  expectation, plus one scalar population variance ``pm_v`` of the peer
  point estimates.  A gossip pull then samples the remote mean from the
  pooled population with the *exact within-class exchangeability
  correction* — the without-replacement variance factor
  ``(N-F)/(N-1)/F`` for ``F`` fanout draws from the ``N = k-1`` other
  exchangeable peers — instead of materializing per-peer rows.  Isolated
  cells are exact in this form (nothing is exchanged, and the decision
  peer's law is unchanged); gossip cells replace the per-peer remote
  mean with its mean-field moment law, validated 3-sigma against the
  per-peer form and the heap oracle (tests/test_fleet.py).  Class-pooled
  noise comes from a third dedicated stream per seed (``_PM_STREAM``),
  so the form is batch-composition-invariant like everything else.

**Heterogeneous peer fleets** (DESIGN.md Sec 7): a cell carrying a
:class:`repro.sim.scenarios.PeerClassMix` stops treating its peers as
interchangeable.  Classes are assigned to slots by the mix's deterministic
prefix-proportional rule, and the engine packs three aggregates that ride
the existing cell batch branchlessly:

* ``hsum_job`` — the sum of hazard multipliers over the k job slots.  The
  job-level failure process stays Poisson (a sum of independent
  exponentials with different rates), but with rate ``hsum_job * mu(t)``
  instead of ``k * mu(t)``.
* ``hsum_watch`` / ``hmean_peer`` — the same aggregate over the watch
  neighbourhood (pooled estimator stream) and the per-peer mean multiplier
  over each peer's ``slot % k`` share (isolated/gossip streams).  The
  estimator itself stays class-blind — it counts deaths against
  slot-seconds of exposure, exactly like the heap's MLE, so both paths
  converge to the *watch-pool mean hazard* and inherit the same bias when
  the job's class mix differs from the watch pool's.
* ``speed`` — the job's aggregate compute speed (mean class speed over the
  k slots: bag-of-tasks load balancing).  A policy interval is wall time;
  the work it commits is ``interval * speed``.

Store cells additionally carry per-class holder columns: replica slot
classes come from the same assignment rule over the R holders, each class
has its own stationary availability ``A_c = 1/(1 + mu h_c t_repair)``, and
the surviving count is drawn mean-field — ``m ~ Binomial(R, mean A_c)``
with restores striped over the survival-weighted mean class uplink.  (The
per-event oracle runs the exact Poisson-binomial holder process; the
mean-field law matches its mean survivor count exactly and its restore
times to first order — see tests/test_heterogeneity.py.)  All columns
reduce bit-exactly to the homogeneous path when every multiplier is 1.0:
``hsum_job == float(k)``, ``speed == 1.0``, and multiplying by 1.0 is
exact in IEEE arithmetic.

**Correlated churn shocks** (DESIGN.md Sec 8): a cell whose scenario, mix,
or :class:`CellSpec.shock` declares a :class:`ShockSpec` adds Poisson
shock epochs at ``rate``, each killing every in-scope peer independently
with probability ``kill_frac`` at the same instant.  The engine carries
this branchlessly and in closed form:

* **job failures** — an epoch kills the job with probability
  ``pkill = 1 - (1-f)^n_scope_job``; Bernoulli-thinning a Poisson process
  is Poisson, so the job-level failure process stays a single exponential
  race with rate ``hsum_job*mu + rate*pkill`` — the same draw ``u`` the
  background path consumes, no extra noise stream and therefore trivially
  batch-composition-invariant.
* **estimator stream** — shock deaths among the watch neighbourhood add
  ``rate * kill_frac * n_scope_watch`` to the pooled expectation feed and
  to each peer's sampled per-share intensity (epoch-level burst clustering
  within one step is folded into the per-step Poisson draw; exactly
  mean-preserving, and the heap oracle delivers true simultaneous bursts
  — the parity suite bounds the difference).
* **store cells** — the i.i.d. ``Binomial(R, A)`` survivor law is replaced
  by the shock-mixture law of ``repro.p2p.overlay.shock_survivor_pmf``: a
  restore was triggered by a shock with probability
  ``q = rate*pkill / (hsum_job*mu + rate*pkill)``, and then finds each
  in-scope holder additionally killed by that same shock — survivors ~
  ``Binomial(R, A*(1-f))`` with ``A`` itself computed at the
  shock-augmented hazard ``mu + rate*f``.  Independence undercounts
  replica loss exactly at restore instants; the mixture is sampled by one
  branchless two-recurrence inverse-CDF unroll from the same ``u2``.
* **macro-stepping is disabled** for shocked cells (like store cells): the
  burst closed form assumes one homogeneous failure process, and a burst
  must never straddle a shock epoch whose estimator burst or replica
  depletion the step needs to see.

Every shock column enters as an additive term that is exactly 0.0 when
``rate == 0``, so ``shock_rate=0`` (and no shock at all) is bit-identical
to the pre-shock path on both backends (tests/test_shocks.py).

**Endogenous restore times** (DESIGN.md Sec 6): a cell carrying a
:class:`repro.p2p.StoreSpec` derives every restore's duration from the
P2P checkpoint store instead of the exogenous ``T_d`` constant.  Each of
the R replica holders is up with the stationary availability
A = 1/(1 + mu(t) * t_repair) (alternating-renewal law, exact for the
memoryless holder process the per-replica heap oracle runs), so the
surviving count is m ~ Binomial(R, A), sampled branchlessly per restore
attempt by unrolling the inverse CDF over ``repro.p2p.store.R_MAX`` terms.
The attempt then lasts ``max(td_up1/m, td_cap)`` seconds (peer-uplink
striping) or ``td_server`` when all replicas are lost (server fallback),
and the engine accounts the aggregate server I/O each cell imposes.
Store cells never macro-step: the burst closed form assumes a constant
restore time, so their survival threshold is treated as 0.

**Fleet-scale execution** (DESIGN.md Sec 9): the cell batch itself scales
with hardware, not with Python:

* **Cell sharding** — on the JAX backend the batch is sharded over the
  data axes of a device mesh with ``jax.shard_map`` (``run_cells(mesh=)``;
  ``"auto"`` builds a 1-D mesh over every local device).  Cells are
  independent, so the per-shard program is the unmodified chunk body with
  no collectives; the batch is padded to the mesh's data extent and the
  padding sliced off the result.  The host-side completion check is
  sharding-aware: each chunk returns its global unfinished count as a
  replicated scalar, so the early-exit loop never gathers the sharded
  state.
* **Fused step kernel** — ``run_cells(step="fused")`` runs the branchless
  ``_attempt`` -> ``_replica_draw`` -> ``_apply`` inner step as one Pallas
  kernel (:mod:`repro.kernels.sim_step`) that keeps the whole carried
  state in VMEM across a chunk of steps and exits early once its block's
  cells are all finished (the stock ``lax.scan`` body, the default,
  cannot).  The kernel consumes pre-generated per-step draws from the
  same key chain as the scan body, so the two paths are bit-identical on
  supported batches (no per-peer-form cells); on CPU it falls back to
  interpret mode.
"""
from __future__ import annotations

import math
import os
from dataclasses import InitVar, dataclass
from typing import NamedTuple, Optional, Sequence

import numpy as np

from repro import tracing
from repro.core.lambertw import lambertw0_numpy
from repro.p2p.store import R_MAX as _R_MAX
from repro.p2p.store import StoreSpec
from repro.p2p.transfer import striped_restore_seconds
from repro.sim.job import SimResult
from repro.sim.scenarios import (
    CONSTANT,
    DIURNAL,
    DOUBLING,
    FLASH_CROWD,
    TRACE,
    PeerClassMix,
    Scenario,
    ShockSpec,
    hazard_kernel,
    resolve_shock,
)

try:  # pragma: no cover - exercised implicitly by backend selection
    import jax
    import jax.numpy as jnp

    _HAVE_JAX = True
except Exception:  # pragma: no cover
    _HAVE_JAX = False

_E = math.e
_POLICY_IDS = {"fixed": 0, "adaptive": 1, "oracle": 2}
_REGIME_IDS = {"pooled": 0, "isolated": 1, "gossip": 2}
DEFAULT_CHUNK = 256
"""Engine steps per jitted call on the JAX backend.

The host loop checks global completion between chunks, so the chunk size
trades compile size and dispatch overhead against wasted post-completion
steps: a larger chunk amortizes dispatch over more steps but runs up to
``chunk - 1`` no-op steps after the last cell finishes.  Override per run
with ``run_cells(chunk=...)`` or process-wide with the
``REPRO_SIM_CHUNK`` environment variable (the keyword wins).  The NumPy
backend checks completion every step and ignores this knob.
"""
_LW_ITERS = 4  # Halley iterations for the per-step W0 (cubic convergence:
               # 3 reaches 1e-14 over the paper's argument range; one spare)
_MACRO_CAP = 1e9  # absolute bound on failures folded into one macro step
_RNG_BLOCK = 256  # numpy backend: uniforms/normals pregenerated per seed
_PEER_CAP = 32    # peer-axis width for the per-peer estimator FORM (the
                  # exact small-k reference; class-pooled moments carry any
                  # larger k).  Fixed (not the batch max) so a cell's
                  # observation noise is invariant to batch composition.
_FANOUT_CAP = 8   # static unroll bound for the gossip pull loop
_POIS_TERMS = 16  # inverse-CDF unroll terms for per-peer death sampling
_POIS_SWITCH = 6.0  # switch to the clipped-normal approximation above this
                    # mean (P[X > 16 | lam = 6] ~ 1e-4, clip bias < 1%)
_OBS_STREAM = 0x6F627376  # numpy backend: per-seed tag of the secondary
                          # stream feeding per-peer observation noise
_PM_STREAM = 0x706D6573   # per-seed tag ("pmes") of the dedicated stream
                          # feeding class-pooled estimator noise (decision-
                          # row deaths + gossip-pull normal), so pooled-form
                          # cells are batch-composition-invariant too
_CLS_CAP = 4      # max peer classes whose replica holders a store cell can
                  # carry (per-class availability columns in the step); also
                  # the class axis of the class-pooled estimator moments
_EXACT_AGG_MAX = 4096  # watch sizes up to this use exact per-slot class
                       # aggregates in _pack; larger fleets take the O(1)
                       # closed forms (O(1/n) quota discretization error)


@dataclass(frozen=True)
class PolicyConfig:
    """Which interval rule a cell runs, plus the adaptive policy's knobs.

    Mirrors the fields of :class:`AdaptiveCheckpointController` /
    :class:`FixedIntervalPolicy` / :class:`OraclePolicy` so a cell spec is a
    complete, hashable description of the policy.

    ``regime`` selects how the adaptive estimator shares information among
    the k job peers (module docstring): ``"pooled"`` (centralized upper
    bound, the default), ``"isolated"`` (per-peer estimators, no
    exchange), or ``"gossip"`` (per-peer estimators that exchange
    estimates every ``gossip_period`` seconds with ``gossip_fanout`` ring
    neighbours, blend weight ``gossip_weight`` — paper Sec 3.1.4).  Only
    meaningful for ``kind="adaptive"``; fixed and oracle policies do not
    estimate.
    """

    kind: str = "adaptive"  # "fixed" | "adaptive" | "oracle"
    fixed_T: float = 600.0
    prior_mu: float = 1.0 / (4 * 3600.0)
    prior_v: float = 10.0
    prior_count: int = 4
    window: int = 32
    min_interval: float = 1.0
    max_interval: float = 24 * 3600.0
    regime: str = "pooled"  # "pooled" | "isolated" | "gossip"
    gossip_period: float = 600.0
    gossip_fanout: int = 2
    gossip_weight: float = 0.5
    # Deprecated cell-spelling aliases (repro.policy migration notes).
    min_iv: InitVar[Optional[float]] = None
    max_iv: InitVar[Optional[float]] = None

    def __post_init__(self, min_iv: Optional[float] = None,
                      max_iv: Optional[float] = None) -> None:
        if min_iv is not None:
            from repro.policy import warn_deprecated_alias
            warn_deprecated_alias("min_iv", "min_interval")
            object.__setattr__(self, "min_interval", float(min_iv))
        if max_iv is not None:
            from repro.policy import warn_deprecated_alias
            warn_deprecated_alias("max_iv", "max_interval")
            object.__setattr__(self, "max_interval", float(max_iv))
        if self.kind not in _POLICY_IDS:
            raise ValueError(f"unknown policy kind {self.kind!r}")
        if self.kind == "fixed" and self.fixed_T <= 0:
            raise ValueError("fixed_T must be positive")
        if self.regime not in _REGIME_IDS:
            raise ValueError(f"unknown estimator regime {self.regime!r}")
        if self.regime != "pooled" and self.kind != "adaptive":
            raise ValueError(
                f"regime {self.regime!r} requires kind='adaptive' "
                f"(fixed/oracle policies do not estimate)")
        if self.gossip_period <= 0:
            raise ValueError("gossip_period must be positive")
        if not 1 <= self.gossip_fanout <= _FANOUT_CAP:
            raise ValueError(f"gossip_fanout must be in [1, {_FANOUT_CAP}]")
        if not 0.0 <= self.gossip_weight <= 1.0:
            raise ValueError("gossip_weight must be in [0, 1]")


@dataclass(frozen=True)
class CellSpec:
    """One simulation cell: a job under a scenario, policy, and seed.

    ``shock`` overrides the correlated-churn shock resolved from the
    scenario/mix (:func:`repro.sim.scenarios.resolve_shock`) — workflow
    stages use it to subject one stage to a shock wave the rest of the
    DAG does not see.
    """

    scenario: Scenario
    policy: PolicyConfig
    seed: int = 0
    k: int = 16
    work: float = 24 * 3600.0
    V: float = 20.0
    T_d: float = 50.0
    watch: Optional[int] = None  # default min(4k, n_slots), like simulate_job
    n_slots: int = 128
    max_wall_time: float = float("inf")
    t0: float = 0.0  # wall-clock offset (workflow stages start mid-scenario)
    store: Optional[StoreSpec] = None  # endogenous T_d from the P2P store
    mix: Optional[PeerClassMix] = None  # heterogeneous fleet composition
    shock: Optional[ShockSpec] = None  # correlated-churn override


def _cell_shock(c: CellSpec) -> Optional[ShockSpec]:
    """The effective shock of a cell: the explicit override, else whichever
    of scenario/mix declares one (ambiguity raises in resolve_shock)."""
    return c.shock if c.shock is not None else resolve_shock(c.scenario, c.mix)


@dataclass(frozen=True)
class BatchResult:
    """Struct-of-arrays result for a cell batch (shapes all [B])."""

    wall_time: np.ndarray
    work_required: np.ndarray
    n_checkpoints: np.ndarray
    n_failures: np.ndarray
    wasted_work: np.ndarray
    checkpoint_time: np.ndarray
    restore_time: np.ndarray
    completed: np.ndarray
    server_bytes: np.ndarray       # I/O imposed on the work-pool server
    n_server_restores: np.ndarray  # restores served by the server fallback
    n_peer_restores: np.ndarray    # restores served from peer replicas
    n_steps: int  # engine steps executed (diagnostic / benchmark)

    def __len__(self) -> int:
        return int(self.wall_time.shape[0])

    def result(self, i: int) -> SimResult:
        """The i-th cell as the reference simulator's :class:`SimResult`."""
        return SimResult(
            wall_time=float(self.wall_time[i]),
            work_required=float(self.work_required[i]),
            n_checkpoints=int(self.n_checkpoints[i]),
            n_failures=int(self.n_failures[i]),
            wasted_work=float(self.wasted_work[i]),
            checkpoint_time=float(self.checkpoint_time[i]),
            restore_time=float(self.restore_time[i]),
            completed=bool(self.completed[i]),
            server_bytes=float(self.server_bytes[i]),
            n_server_restores=int(self.n_server_restores[i]),
            n_peer_restores=int(self.n_peer_restores[i]),
        )


class _Params(NamedTuple):
    """Packed per-cell constants (all shape [B] except the trace tables)."""

    pol: np.ndarray          # policy kind id
    regime: np.ndarray       # estimator regime id (pooled/isolated/gossip)
    g_period: np.ndarray     # gossip exchange period (s)
    g_fanout: np.ndarray     # gossip ring partners per round (float for jit)
    g_weight: np.ndarray     # blend weight of remote estimates
    fixed_T: np.ndarray
    prior_mu: np.ndarray
    prior_v: np.ndarray
    prior_count: np.ndarray
    window: np.ndarray       # estimator window K (adaptive macro-burst cap)
    log_decay: np.ndarray    # log(1 - 1/window): estimator decay per death
    min_interval: np.ndarray
    max_interval: np.ndarray
    k: np.ndarray
    work: np.ndarray
    V: np.ndarray
    T_d: np.ndarray
    watch: np.ndarray
    max_wall: np.ndarray
    t0: np.ndarray
    scen_kind: np.ndarray
    scen_p: np.ndarray       # [B, 4]
    trace_t: np.ndarray      # [B, L]
    trace_mtbf: np.ndarray   # [B, L]
    trace_min_gap: np.ndarray
    store_on: np.ndarray     # bool: T_d is endogenous (P2P store cell)
    R: np.ndarray            # replica count (float for jit)
    repair: np.ndarray       # holder re-replication time
    td_up1: np.ndarray       # img / peer_uplink  (one-source restore)
    td_cap: np.ndarray       # img / peer_downlink (striping floor)
    td_srv: np.ndarray       # img / server_share (all-replicas-lost)
    img_bytes: np.ndarray    # checkpoint image size (server accounting)
    hsum_job: np.ndarray     # sum of hazard multipliers over the k job slots
    hsum_watch: np.ndarray   # same over the watch neighbourhood
    hmean_peer: np.ndarray   # [B, _PEER_CAP] mean multiplier per peer's share
    speed: np.ndarray        # job compute speed (work units per wall second)
    store_mix: np.ndarray    # bool: replica holders carry per-class columns
    cls_n: np.ndarray        # [B, _CLS_CAP] holder count per class
    cls_h: np.ndarray        # [B, _CLS_CAP] hazard multiplier per class
    cls_td1: np.ndarray      # [B, _CLS_CAP] one-source restore per class (s)
    shock_rate: np.ndarray   # correlated shock epochs per second
    shock_pkill: np.ndarray  # P(an epoch kills >= 1 job peer)
    shock_dwatch: np.ndarray  # E[watched deaths per epoch] = f * n_scope_watch
    shock_dpeer: np.ndarray  # [B, _PEER_CAP] E[deaths/epoch] per peer's share
    shock_f: np.ndarray      # holder kill fraction (homogeneous store cells)
    cls_f: np.ndarray        # [B, _CLS_CAP] holder kill fraction per class
    shocked: np.ndarray      # bool: rate > 0 (disables macro-stepping)
    pm_on: np.ndarray        # bool: estimator carried in class-pooled form
    pm_nc: np.ndarray        # [B, _CLS_CAP] non-decision peers per class
    pm_rate: np.ndarray      # [B, _CLS_CAP] mean watch-share hazard mult of
                             # a class-c peer (fleet mean for huge fleets)
    pm_shock: np.ndarray     # [B, _CLS_CAP] E[shock deaths/epoch] seen by a
                             # class-c peer's watch share


class _State(NamedTuple):
    """Per-cell mutable simulation state (floats for jit).

    All arrays are shape [B] except the estimator state: ``ema_d`` /
    ``ema_T`` / ``mu0`` / ``td_obs`` carry a trailing peer axis of width
    ``_PEER_CAP`` when any cell in the batch runs the per-peer form
    (width 1 otherwise), and the class-pooled moments ``pm_d`` / ``pm_T``
    / ``pm_mu0`` carry a trailing class axis of width ``_CLS_CAP`` (inert
    zeros for cells not in that form).  Peer slot 0 is the *decision
    peer*: the job's checkpoint interval is computed from its estimates
    in every regime and both forms.
    """

    t: np.ndarray            # absolute wall clock (starts at t0)
    done: np.ndarray         # committed work
    in_restore: np.ndarray   # bool
    finished: np.ndarray     # bool
    censored: np.ndarray     # bool
    n_ckpt: np.ndarray
    n_fail: np.ndarray
    wasted: np.ndarray
    ckpt_time: np.ndarray
    restore_time: np.ndarray
    ema_d: np.ndarray        # [B, P] decayed observed-death count (estimator)
    ema_T: np.ndarray        # [B, P] decayed observed exposure (slot-seconds)
    mu0: np.ndarray          # [B, P] per-peer prior center (gossip re-seeds)
    seen_ckpt: np.ndarray    # bool: V has been measured
    seen_restore: np.ndarray  # bool: T_d has been measured
    td_obs: np.ndarray       # [B, P] last observed restore duration
    next_g: np.ndarray       # wall time of the next gossip round
    n_round: np.ndarray      # gossip rounds done (drives the cyclic schedule)
    sv_bytes: np.ndarray     # server I/O imposed so far
    n_srv: np.ndarray        # restores served by the server fallback
    n_peer: np.ndarray       # restores served from peer replicas
    pm_d: np.ndarray         # [B, _CLS_CAP] class-mean decayed death count
    pm_T: np.ndarray         # [B, _CLS_CAP] class-mean decayed exposure
    pm_mu0: np.ndarray       # [B, _CLS_CAP] class prior center (gossip
                             # rounds re-seed it at the merged estimate)
    pm_v: np.ndarray         # population variance of the k-1 non-decision
                             # peers' point estimates (class-pooled form)


def _scope_weight(sk: ShockSpec, mix: Optional[PeerClassMix]) -> float:
    """Fraction of slots a shock's scope covers under the mix's quota
    assignment — the O(1) closed form of ``mean(scope_mask)`` (exact up to
    the O(1/n) quota discretization the mask itself carries).  Replicates
    ``scope_mask``'s scope validation so huge fleets fail identically."""
    if sk.scope == "all":
        return 1.0
    if mix is None:
        raise ValueError(
            f"class-scoped shock {sk.scope!r} needs a PeerClassMix")
    names = [pc.name for pc in mix.classes]
    if sk.scope not in names:
        raise ValueError(
            f"shock scope {sk.scope!r} names no class of the mix "
            f"{sorted(names)}")
    return float(mix.weights[names.index(sk.scope)])


def _pack(cells: Sequence[CellSpec], peer_form: str = "auto") -> _Params:
    B = len(cells)
    if B == 0:
        raise ValueError("need at least one cell")
    if peer_form not in ("auto", "perpeer", "pm"):
        raise ValueError(f"unknown peer_form {peer_form!r}")
    f = lambda vals: np.asarray(vals, dtype=np.float64)
    watch = [min(4 * c.k, c.n_slots) if c.watch is None
             else min(c.watch, c.n_slots) for c in cells]
    # Which estimator form carries each non-pooled cell (module docstring):
    # per-peer rows up to _PEER_CAP, class-pooled moments beyond — or force
    # one form batch-wide with peer_form ("perpeer" keeps the historical
    # hard cap; "pm" is how the parity suite pits the forms against each
    # other at small k).
    pm_on_l = []
    for c in cells:
        nonpooled = c.policy.regime != "pooled"
        if peer_form == "pm":
            pm = nonpooled
        else:
            pm = nonpooled and c.k > _PEER_CAP
            if pm and peer_form == "perpeer":
                raise ValueError(
                    f"per-peer estimator form supports k <= {_PEER_CAP}, "
                    f"got k={c.k} (use peer_form='auto' or 'pm' for the "
                    f"class-pooled form)")
        if (pm and c.mix is not None and not c.mix.is_trivial
                and len(c.mix) > _CLS_CAP):
            raise ValueError(
                f"class-pooled estimator supports mixes of <= {_CLS_CAP} "
                f"classes, got {len(c.mix)}")
        pm_on_l.append(pm)
    for c in cells:
        if c.k > c.n_slots:
            raise ValueError(f"job needs {c.k} slots but network has {c.n_slots}")
        if (c.mix is not None and c.store is not None
                and not c.mix.is_trivial and len(c.mix) > _CLS_CAP):
            raise ValueError(
                f"store cells support mixes of <= {_CLS_CAP} classes, "
                f"got {len(c.mix)}")
    # Heterogeneous-fleet aggregates.  Trivial mixes (every multiplier 1.0)
    # take the exact homogeneous values — hsum_job == float(k) etc. — so a
    # single-baseline-class mix is bit-identical to no mix at all.
    hsum_job = np.empty(B)
    hsum_watch = np.empty(B)
    hmean_peer = np.ones((B, _PEER_CAP))
    speed = np.ones(B)
    store_mix = np.zeros(B, dtype=bool)
    cls_n = np.zeros((B, _CLS_CAP))
    cls_h = np.ones((B, _CLS_CAP))
    cls_td1 = np.ones((B, _CLS_CAP))
    for i, c in enumerate(cells):
        mix = c.mix
        if mix is None or mix.is_trivial:
            hsum_job[i] = float(c.k)
            hsum_watch[i] = float(watch[i])
            continue
        if watch[i] <= _EXACT_AGG_MAX:
            hm = np.asarray(mix.hazard_mults(watch[i]))
            hsum_job[i] = math.fsum(hm[:c.k])
            hsum_watch[i] = math.fsum(hm)
            speed[i] = mix.mean_speed(c.k)
            for j in range(min(c.k, _PEER_CAP)):
                hmean_peer[i, j] = float(np.mean(hm[j::c.k]))
        else:
            # Fleet-scale closed forms: the quota assignment puts weight
            # w_c of any long slot range in class c (±1 slot), so every
            # aggregate collapses to a weight-dot — O(#classes) instead of
            # O(watch) Python, with O(1/watch) discretization error.
            w = np.asarray(mix.weights)
            hbar = float(w @ [pc.hazard_mult for pc in mix.classes])
            hsum_job[i] = c.k * hbar
            hsum_watch[i] = watch[i] * hbar
            speed[i] = float(w @ [pc.speed for pc in mix.classes])
            hmean_peer[i, :min(c.k, _PEER_CAP)] = hbar
        if c.store is not None and c.store.R > 0:
            store_mix[i] = True
            for cls_idx in mix.assign(c.store.R):
                cls_n[i, cls_idx] += 1.0
            for ci, pc in enumerate(mix.classes):
                cls_h[i, ci] = pc.hazard_mult
                cls_td1[i, ci] = c.store.td_up1 / pc.uplink_mult
    # Correlated-churn shock columns (DESIGN.md Sec 8).  All-zero for
    # unshocked cells, and every consumer folds them in as additive terms
    # that are exactly 0.0 then — the basis of the shock_rate=0
    # bit-identity contract.
    shock_rate = np.zeros(B)
    shock_pkill = np.zeros(B)
    shock_dwatch = np.zeros(B)
    shock_dpeer = np.zeros((B, _PEER_CAP))
    shock_f = np.zeros(B)
    cls_f = np.zeros((B, _CLS_CAP))
    shocked = np.zeros(B, dtype=bool)
    for i, c in enumerate(cells):
        sk = _cell_shock(c)
        if sk is None:
            continue
        shock_rate[i] = sk.rate
        shocked[i] = sk.rate > 0.0
        if watch[i] <= _EXACT_AGG_MAX:
            # Validates class scopes against the cell's mix; the mask over
            # the watch prefix also covers the k job slots (prefix
            # assignment).
            mask = sk.scope_mask(c.mix, watch[i])
            shock_pkill[i] = sk.job_kill_prob(sum(mask[:c.k]))
            shock_dwatch[i] = sk.kill_frac * sum(mask)
            dpeer = [sk.kill_frac * sum(mask[j::c.k])
                     for j in range(min(c.k, _PEER_CAP))]
        else:
            # Closed forms again (see the hazard aggregates above): a scope
            # covers weight-w_scope of any long slot range, so per-share
            # in-scope counts are w_scope * share size.
            w_scope = _scope_weight(sk, c.mix)
            shock_pkill[i] = sk.job_kill_prob(c.k * w_scope)
            shock_dwatch[i] = sk.kill_frac * watch[i] * w_scope
            dpeer = [sk.kill_frac * (watch[i] / c.k) * w_scope
                     for j in range(min(c.k, _PEER_CAP))]
        if c.policy.regime == "pooled":
            shock_dpeer[i, :] = shock_dwatch[i]  # only peer slot 0 is live
        else:
            # Exact in-scope count of peer j's slot share j::k (fleet-mean
            # share above the exact-aggregate cutoff).
            shock_dpeer[i, :len(dpeer)] = dpeer
        if c.store is not None and c.store.R > 0:
            # A class scope on a TRIVIAL multi-class mix (identical
            # baseline classes used as partition groups) still shocks only
            # part of the holder fleet — the homogeneous shock_f column
            # cannot express that, so such cells take the per-class path
            # too (cls_h/cls_td1 are all-1.0 there, so the only difference
            # from homogeneous is the scoped kill fraction — matching the
            # scope-masked per-event oracle).
            partial = (sk.scope != "all" and c.mix is not None
                       and len(c.mix) > 1)
            if partial or (c.mix is not None and not c.mix.is_trivial):
                if len(c.mix) > _CLS_CAP:
                    raise ValueError(
                        f"store cells support mixes of <= {_CLS_CAP} "
                        f"classes, got {len(c.mix)}")
                if not store_mix[i]:  # trivial mix skipped the columns
                    store_mix[i] = True
                    for cls_idx in c.mix.assign(c.store.R):
                        cls_n[i, cls_idx] += 1.0
                    for ci, pc in enumerate(c.mix.classes):
                        cls_h[i, ci] = pc.hazard_mult
                        cls_td1[i, ci] = c.store.td_up1 / pc.uplink_mult
                for ci, pc in enumerate(c.mix.classes):
                    if sk.scope in ("all", pc.name):
                        cls_f[i, ci] = sk.kill_frac
            else:
                # Homogeneous holders (no mix, or a scope covering the
                # whole single-class fleet): one fleet-wide kill fraction.
                shock_f[i] = sk.kill_frac
    # Class-pooled estimator columns (module docstring; DESIGN.md Sec 9).
    # pm_nc/pm_rate/pm_shock describe the k-1 non-decision peers grouped by
    # peer class: how many, the mean class multiplier of each one's watch
    # share, and the shock-death intensity its share sees.  Small fleets
    # compute them exactly from the quota assignment (so the pm form sees
    # the same per-share composition the per-peer form samples from);
    # fleet-scale cells take the weight-dot closed forms.
    pm_on = np.asarray(pm_on_l, dtype=bool)
    pm_nc = np.zeros((B, _CLS_CAP))
    pm_rate = np.ones((B, _CLS_CAP))
    pm_shock = np.zeros((B, _CLS_CAP))
    for i, c in enumerate(cells):
        if not pm_on_l[i]:
            continue
        sk = _cell_shock(c)
        f_kill = sk.kill_frac if sk is not None else 0.0
        mix = c.mix
        if mix is None or len(mix) == 1:
            # One exchangeable class.  With no class structure the scope is
            # "all" (scope_mask validates that), so the mean in-scope count
            # of a non-decision share is exact: the decision peer holds
            # ceil(watch/k) of the watch slots and the rest split the
            # remainder evenly in distribution.
            pm_nc[i, 0] = c.k - 1
            if mix is not None:
                pm_rate[i, 0] = mix.classes[0].hazard_mult
            pm_shock[i, 0] = (f_kill * (watch[i] - math.ceil(watch[i] / c.k))
                              / max(c.k - 1, 1))
        elif c.k <= _EXACT_AGG_MAX and watch[i] <= _EXACT_AGG_MAX:
            asg = mix.assign(c.k)
            hm = np.asarray(mix.hazard_mults(watch[i]))
            msk = (np.asarray(sk.scope_mask(mix, watch[i]), dtype=np.float64)
                   if sk is not None else None)
            for ci in range(len(mix)):
                js = [j for j in range(1, c.k) if asg[j] == ci]
                pm_nc[i, ci] = len(js)
                if js:
                    pm_rate[i, ci] = float(np.mean(
                        [np.mean(hm[j::c.k]) for j in js]))
                    if msk is not None:
                        pm_shock[i, ci] = f_kill * float(np.mean(
                            [msk[j::c.k].sum() for j in js]))
        else:
            # Fleet-scale closed forms: shares homogenize to the fleet-mean
            # multiplier and in-scope fraction, class counts to the quota
            # weights (normalized so they sum to exactly k-1).
            w = np.asarray(mix.weights)
            hbar = float(w @ [pc.hazard_mult for pc in mix.classes])
            w_scope = _scope_weight(sk, mix) if sk is not None else 0.0
            for ci in range(len(mix)):
                pm_nc[i, ci] = w[ci] * (c.k - 1)
                pm_rate[i, ci] = hbar
                pm_shock[i, ci] = f_kill * (watch[i] / c.k) * w_scope
    L = max(2, max(len(c.scenario.trace_t) for c in cells))
    trace_t = np.zeros((B, L))
    trace_mtbf = np.ones((B, L))
    min_gap = np.full(B, np.inf)
    for i, c in enumerate(cells):
        tt, tm = c.scenario.trace_t, c.scenario.trace_mtbf
        if tt:
            n = len(tt)
            trace_t[i, :n] = tt
            trace_mtbf[i, :n] = tm
            trace_t[i, n:] = tt[-1] + np.arange(1, L - n + 1)  # keep ascending
            trace_mtbf[i, n:] = tm[-1]
            if n > 1:
                min_gap[i] = float(np.min(np.diff(tt)))
    return _Params(
        pol=np.asarray([_POLICY_IDS[c.policy.kind] for c in cells], dtype=np.int64),
        regime=np.asarray([_REGIME_IDS[c.policy.regime] for c in cells],
                          dtype=np.int64),
        g_period=f([c.policy.gossip_period for c in cells]),
        g_fanout=f([c.policy.gossip_fanout for c in cells]),
        g_weight=f([c.policy.gossip_weight for c in cells]),
        fixed_T=f([c.policy.fixed_T for c in cells]),
        prior_mu=f([c.policy.prior_mu for c in cells]),
        prior_v=f([c.policy.prior_v for c in cells]),
        prior_count=f([c.policy.prior_count for c in cells]),
        window=f([c.policy.window for c in cells]),
        log_decay=f([math.log1p(-1.0 / c.policy.window) for c in cells]),
        min_interval=f([c.policy.min_interval for c in cells]),
        max_interval=f([c.policy.max_interval for c in cells]),
        k=f([c.k for c in cells]),
        work=f([c.work for c in cells]),
        V=f([c.V for c in cells]),
        T_d=f([c.T_d for c in cells]),
        watch=f(watch),
        max_wall=f([c.max_wall_time for c in cells]),
        t0=f([c.t0 for c in cells]),
        scen_kind=np.asarray([c.scenario.kind for c in cells], dtype=np.int64),
        scen_p=f([c.scenario.params for c in cells]),
        trace_t=trace_t,
        trace_mtbf=trace_mtbf,
        trace_min_gap=min_gap,
        store_on=np.asarray([c.store is not None for c in cells], dtype=bool),
        R=f([c.store.R if c.store else 0 for c in cells]),
        repair=f([c.store.t_repair if c.store else 1.0 for c in cells]),
        td_up1=f([c.store.td_up1 if c.store else c.T_d for c in cells]),
        td_cap=f([c.store.td_cap if c.store else c.T_d for c in cells]),
        td_srv=f([c.store.td_server if c.store else c.T_d for c in cells]),
        img_bytes=f([c.store.transfer.img_bytes if c.store else 0.0
                     for c in cells]),
        hsum_job=hsum_job,
        hsum_watch=hsum_watch,
        hmean_peer=hmean_peer,
        speed=speed,
        store_mix=store_mix,
        cls_n=cls_n,
        cls_h=cls_h,
        cls_td1=cls_td1,
        shock_rate=shock_rate,
        shock_pkill=shock_pkill,
        shock_dwatch=shock_dwatch,
        shock_dpeer=shock_dpeer,
        shock_f=shock_f,
        cls_f=cls_f,
        shocked=shocked,
        pm_on=pm_on,
        pm_nc=pm_nc,
        pm_rate=pm_rate,
        pm_shock=pm_shock,
    )


def _init_state(p: _Params, xp, n_peer: int) -> _State:
    B = p.k.shape[0]
    zeros = xp.zeros(B)
    false = xp.zeros(B, dtype=bool)
    zeros_p = xp.zeros((B, n_peer))
    zeros_c = xp.zeros((B, _CLS_CAP))
    return _State(t=xp.asarray(p.t0), done=zeros, in_restore=false,
                  finished=false, censored=false, n_ckpt=zeros, n_fail=zeros,
                  wasted=zeros, ckpt_time=zeros, restore_time=zeros,
                  ema_d=zeros_p, ema_T=zeros_p,
                  mu0=zeros_p + p.prior_mu[:, None],
                  seen_ckpt=false, seen_restore=false,
                  td_obs=zeros_p + p.T_d[:, None],
                  next_g=p.t0 + p.g_period, n_round=zeros,
                  sv_bytes=zeros, n_srv=zeros, n_peer=zeros,
                  pm_d=zeros_c, pm_T=zeros_c,
                  pm_mu0=zeros_c + p.prior_mu[:, None], pm_v=zeros)


def _opt_interval(mu, k, V, T_d, xp, lw):
    """Vectorized 1/lambda* (paper Sec 3.2.3), inf at the V->0 branch point."""
    # The stacked adaptive+oracle call passes mu as [2, B] with k still [B]:
    # spell the rank extension out so the engine stays clean under
    # jax_numpy_rank_promotion="raise" (strict-runtime CI lane).
    kmu = xp.broadcast_to(k, xp.shape(mu)) * mu
    arg = (V * kmu - T_d * kmu - 1.0) / (T_d * kmu + 1.0) / _E
    x = lw(arg) + 1.0
    return xp.where(x > 0.0, x / kmu, xp.inf)


def _coherence(t, p: _Params, xp):
    """How far ahead the hazard can be treated as locally constant.

    Bounds macro-step jumps so time-varying scenarios keep their shape:
    within the returned horizon mu(t) changes by <~10%.
    """
    p1, p2, p3 = p.scen_p[..., 1], p.scen_p[..., 2], p.scen_p[..., 3]
    inf = xp.inf
    c_doub = p1 / 8.0
    c_diur = p2 / 32.0
    c_flash = xp.where(t < p2, p2 - t, xp.where(t < p2 + p3, p2 + p3 - t, inf))
    c_trace = p.trace_min_gap / 4.0
    return xp.where(p.scen_kind == DOUBLING, c_doub,
           xp.where(p.scen_kind == DIURNAL, c_diur,
           xp.where(p.scen_kind == FLASH_CROWD, c_flash,
           xp.where(p.scen_kind == TRACE, c_trace, inf))))


def _trunc_exp_moments(kmu, L, q, xp):
    """Mean/variance of X ~ Exp(kmu) conditioned on X < L; q = exp(-kmu L)."""
    inv = 1.0 / kmu
    ratio = q / xp.maximum(1.0 - q, 1e-300)
    m = inv - L * ratio
    ex2 = 2.0 * inv * inv - (L * L + 2.0 * L * inv) * ratio
    v = xp.maximum(ex2 - m * m, 0.0)
    return m, v


def _replica_draw(mu, u2, p: _Params, xp, any_het: bool, any_shock: bool,
                  kmu_bg, srate):
    """Endogenous restore law: sample the surviving replica count and turn
    it into this attempt's restore duration (DESIGN.md Sec 6).

    Each holder is up with the stationary availability A = 1/(1 + mu * t_r)
    (alternating renewal; exact vs the per-replica heap oracle because the
    holder process is memoryless and started stationary), so m ~
    Binomial(R, A).  The inverse CDF is unrolled over R_MAX terms with the
    pmf recurrence pmf_{j+1} = pmf_j * (R-j)/(j+1) * A/(1-A) — branchless,
    so store and legacy cells share one jitted step.

    ``any_het`` (static) enables the heterogeneous-holder columns: a store
    cell with a :class:`PeerClassMix` gives holder class c the availability
    A_c = 1/(1 + mu h_c t_repair), and the draw goes mean-field —
    Binomial(R, mean A_c) with restores striped over the survival-weighted
    mean class uplink (the per-event oracle's Poisson-binomial has the same
    mean survivor count; the spread difference is second-order, see
    DESIGN.md Sec 7).  Non-mix cells keep the exact legacy formula bit-for-
    bit (both paths are computed and selected with ``where``).

    ``any_shock`` (static) switches the survivor draw to the shock-mixture
    law of :func:`repro.p2p.overlay.shock_survivor_pmf` (DESIGN.md Sec 8):
    the attempt follows a shock-caused failure with probability
    ``q = srate / (kmu_bg + srate)`` and then finds each in-scope holder
    additionally killed by that same shock — the mixture
    ``q * Binom(R, A*(1-f)) + (1-q) * Binom(R, A)`` is sampled by running
    both pmf recurrences and inverting the mixed CDF with the SAME ``u2``,
    so no extra noise stream is consumed.  ``A`` itself carries the
    shock-augmented holder hazard ``mu + rate*f``.  All shock terms are
    additive zeros at rate 0, so the mixture collapses to the i.i.d. law
    bit-for-bit there.

    Returns (td_rest, from_server, td_expect): the sampled attempt duration
    (legacy cells keep p.T_d), whether it hits the server fallback, and
    E[td] for the oracle policy.
    """
    A_hom = xp.clip(1.0 / (1.0 + mu * p.repair
                           + (p.shock_rate * p.shock_f) * p.repair),
                    1e-12, 1.0 - 1e-12)
    A = A_hom
    td_up1 = p.td_up1
    A2_mix = td2_mix = None
    if any_het:
        A_c = (1.0 / (1.0 + (mu * p.repair)[..., None] * p.cls_h
                      + (p.shock_rate * p.repair)[..., None] * p.cls_f))
        nA = p.cls_n * A_c                    # expected survivors per class
        sumA = xp.sum(nA, axis=-1)
        A_mix = xp.clip(sumA / xp.maximum(p.R, 1.0), 1e-12, 1.0 - 1e-12)
        td_mix = sumA / xp.maximum(xp.sum(nA / p.cls_td1, axis=-1), 1e-300)
        A = xp.where(p.store_mix, A_mix, A)
        td_up1 = xp.where(p.store_mix, td_mix, td_up1)
        if any_shock:
            # Post-shock per-class survival: the same shock that killed the
            # job also killed each in-scope holder w.p. f_c.
            nA2 = nA * (1.0 - p.cls_f)
            sumA2 = xp.sum(nA2, axis=-1)
            A2_mix = xp.clip(sumA2 / xp.maximum(p.R, 1.0), 0.0, 1.0 - 1e-12)
            td2_mix = sumA2 / xp.maximum(xp.sum(nA2 / p.cls_td1, axis=-1),
                                         1e-300)
    if any_shock:
        q = srate / xp.maximum(kmu_bg + srate, 1e-300)
        A2 = A_hom * (1.0 - p.shock_f)
        if any_het:
            A2 = xp.where(p.store_mix, A2_mix, A2)
            # Mixture-weighted stripe bandwidth (mean-field): exactly
            # td_up1 at q=0, and the survival-weighted post-shock uplink
            # otherwise.
            td_up1 = xp.where(p.store_mix,
                              (1.0 - q) * td_up1 + q * td2_mix, td_up1)
        ratio_b = A2 / (1.0 - A2)
        pmf_b = (1.0 - A2) ** p.R
    ratio = A / (1.0 - A)
    pmf_a = (1.0 - A) ** p.R
    pmf = (1.0 - q) * pmf_a + q * pmf_b if any_shock else pmf_a  # P(m = 0)
    cdf = pmf
    m = xp.zeros_like(mu)
    etd = pmf * p.td_srv                      # E[td] accumulator: m=0 term
    for j in range(_R_MAX):
        m = m + (u2 > cdf)
        pmf_a = xp.maximum(pmf_a * (p.R - j) / (j + 1.0) * ratio, 0.0)
        if any_shock:
            pmf_b = xp.maximum(pmf_b * (p.R - j) / (j + 1.0) * ratio_b, 0.0)
            pmf = (1.0 - q) * pmf_a + q * pmf_b
        else:
            pmf = pmf_a
        cdf = cdf + pmf
        etd = etd + pmf * striped_restore_seconds(j + 1.0, td_up1,
                                                  p.td_cap, p.td_srv, xp)
    m = xp.minimum(m, p.R)                    # guard pmf underflow at A ~ 1
    td_endo = striped_restore_seconds(m, td_up1, p.td_cap, p.td_srv, xp)
    td_rest = xp.where(p.store_on, td_endo, p.T_d)
    from_server = p.store_on & (m < 1.0)
    td_expect = xp.where(p.store_on, etd, p.T_d)
    return td_rest, from_server, td_expect


def _attempt(s: _State, p: _Params, u2, xp, lw, any_store: bool,
             any_het: bool, any_shock: bool):
    """Pure pre-sampling half of a step: what is each cell about to do?

    ``u2`` is this step's replica-survival uniform (store cells sample the
    surviving holder count from it; legacy cells ignore it).  ``any_store``
    / ``any_het`` / ``any_shock`` are static per batch: all-legacy batches
    skip the R_MAX-term replica unroll entirely, all-homogeneous-store
    batches skip the per-class availability columns, all-unshocked batches
    skip the second mixture recurrence (the u2 stream is still consumed so
    a cell's realization never depends on batch composition).
    """
    mu = hazard_kernel(s.t, p.scen_kind, p.scen_p, p.trace_t, p.trace_mtbf, xp)
    # The job-level failure process under a class mix: each slot fails at
    # mu * h_slot, and a sum of independent exponentials is Poisson with
    # the summed rate — hsum_job == float(k) for homogeneous cells.
    kmu_bg = p.hsum_job * mu
    # Correlated shocks (DESIGN.md Sec 8): job-killing epochs are the
    # Bernoulli-thinned shock Poisson process (rate * pkill), and the
    # superposition with the background process is again Poisson — one
    # exponential race, same ``u`` draw, +0.0 exactly when unshocked.
    srate = p.shock_rate * p.shock_pkill
    kmu = kmu_bg + srate
    active = ~s.finished
    # Censoring is checked before EVERY attempt — work cycles and restore
    # retries alike, matching simulate_job: under shock-dominated churn
    # the retry loop is exactly where a censored cell would otherwise burn
    # unbounded steps (expected retries grow like exp(rate * T_d)).
    censor_now = active & (s.t - p.t0 > p.max_wall)
    att = active & ~censor_now

    if any_store:
        td_rest, from_server, td_expect = _replica_draw(mu, u2, p, xp,
                                                        any_het, any_shock,
                                                        kmu_bg, srate)
    else:
        td_rest, from_server, td_expect = p.T_d, p.store_on, p.T_d

    # Policy intervals — all three computed, selected branchlessly.  The
    # adaptive and oracle Lambert-W evaluations are stacked into one call:
    # the W iterations dominate per-step transcendental count.  Decisions
    # come from peer slot 0 (the decision peer) in every estimator regime;
    # pooled cells keep all their estimator state in that slot.
    mu_hat = ((s.ema_d[:, 0] + p.prior_count)
              / (s.ema_T[:, 0] + p.prior_count / s.mu0[:, 0]))
    V_hat = xp.where(s.seen_ckpt, p.V, p.prior_v)
    # Adaptive cells mirror observe_restore: the last measured restore
    # duration (endogenous for store cells); oracle cells know the law and
    # use E[td] under the true availability.
    td_known = xp.where(p.store_on, s.td_obs[:, 0], p.T_d)
    Td_hat = xp.where(s.seen_restore, td_known, V_hat)
    # The oracle knows the fleet composition AND the shock process: its
    # per-peer rate is the class-mean hazard hsum_job/k * mu plus the
    # job-killing shock rate spread over the k peers (srate/k is exactly
    # 0.0 for unshocked cells, so the sum is bit-identical there).  The
    # adaptive estimate mu_hat already converges to the watch-pool mean
    # of the same effective rate.
    mu_true = mu * (p.hsum_job / p.k) + srate / p.k
    iv2 = _opt_interval(
        xp.stack([mu_hat, mu_true]), p.k,
        xp.stack([xp.maximum(V_hat, 1e-6), p.V]),
        xp.stack([Td_hat, td_expect]), xp, lw)
    iv_adaptive = xp.clip(iv2[0], p.min_interval, p.max_interval)
    # The oracle is clamped exactly like the adaptive policy (and like the
    # heap's OraclePolicy): an unclipped oracle conflates policy quality
    # with clipping in every comparison grid.
    iv_oracle = xp.clip(iv2[1], p.min_interval, p.max_interval)
    interval = xp.where(p.pol == 0, p.fixed_T,
                        xp.where(p.pol == 1, iv_adaptive, iv_oracle))
    interval = xp.maximum(interval, 1e-3)

    remaining = xp.maximum(p.work - s.done, 0.0)
    # A policy interval is wall-clock compute time; the work it commits is
    # interval * speed (speed == 1.0, exactly, for homogeneous cells).
    work_target = xp.minimum(interval * p.speed, remaining)
    is_final = work_target >= remaining
    cycle_len = work_target / p.speed + xp.where(is_final, 0.0, p.V)
    attempt_len = xp.where(s.in_restore, td_rest, cycle_len)
    return (mu, kmu, attempt_len, work_target, is_final, cycle_len,
            censor_now, att, td_rest, from_server)


def _sample_counts(lam, u3, z3, xp):
    """Per-peer observed-death counts ~ Poisson(lam), branchless.

    Small means (the common case: one checkpoint cycle's worth of deaths in
    a watch/k slice) use an inverse-CDF unroll over ``_POIS_TERMS`` terms
    driven by the uniform ``u3``; means above ``_POIS_SWITCH`` switch to the
    clipped-normal approximation driven by ``z3`` (clip bias < 1% there).
    Both transforms are per-element, so same-seed cells share the underlying
    draws (common random numbers) while each applies its own rate.
    """
    lam_s = xp.minimum(lam, _POIS_SWITCH)
    pmf = xp.exp(-lam_s)
    cdf = pmf
    d = xp.zeros_like(lam)
    for j in range(_POIS_TERMS):
        d = d + (u3 > cdf)
        pmf = pmf * lam_s / (j + 1.0)
        cdf = cdf + pmf
    d_norm = xp.maximum(lam + xp.sqrt(xp.maximum(lam, 0.0)) * z3, 0.0)
    return xp.where(lam > _POIS_SWITCH, d_norm, d)


def _gossip_mix(s_t, ema_d, ema_T, mu0, n_round, next_g, finished,
                peer_act, p: _Params, xp):
    """One epidemic exchange round for cells whose gossip clock is due.

    Mirrors ``AdaptiveCheckpointController.ingest_gossip`` per peer: each
    peer pulls the current mu point estimates of ``g_fanout`` ring
    neighbours (deterministic cyclic schedule — offset 1 + (round*fanout +
    f) mod (k-1), a circulant doubly stochastic mixing matrix, identical
    to the heap oracle's ``GossipAdaptivePolicy``), blends merged =
    (1-w)*local + w*remote_mean, and re-seeds its window at the merged
    value (ema_d = ema_T = 0, prior center mu0 = merged) so subsequent
    local observations keep moving it.  Only mu is exchanged: V and T_d
    are job-level stalls every peer observes identically (the heap
    oracle's ``ingest_gossip`` blends of equal values are no-ops), so
    there is nothing to mix.
    """
    due = (p.regime == _REGIME_IDS["gossip"]) & ~finished & (s_t >= next_g)
    P = ema_d.shape[1]
    mu_hat = (ema_d + p.prior_count[:, None]) / (
        ema_T + p.prior_count[:, None] / mu0)
    idx = xp.arange(P)[None, :]
    kk = xp.maximum(p.k, 1.0)[:, None]
    km1 = xp.maximum(p.k - 1.0, 1.0)
    rem_mu = xp.zeros_like(mu_hat)
    for f in range(_FANOUT_CAP):
        off = 1.0 + ((n_round * p.g_fanout + f) % km1)
        # Clamp to the materialized peer axis: per-peer cells always have
        # j < k <= P, so this only guards class-pooled cells (k may exceed
        # P) riding a mixed batch — their result is overridden anyway.
        j = xp.minimum((idx + off[:, None]) % kk,
                       float(P - 1)).astype(p.regime.dtype)
        in_f = (f < p.g_fanout)[:, None]
        rem_mu = rem_mu + xp.where(in_f,
                                   xp.take_along_axis(mu_hat, j, axis=1), 0.0)
    w = p.g_weight[:, None]
    merged_mu = (1.0 - w) * mu_hat + w * rem_mu / p.g_fanout[:, None]
    upd = due[:, None] & peer_act
    return (xp.where(upd, 0.0, ema_d),
            xp.where(upd, 0.0, ema_T),
            xp.where(upd, merged_mu, mu0),
            n_round + due,
            xp.where(due, s_t + p.g_period, next_g))


def _pool_update(s: _State, p: _Params, t, elapsed, mu, finished,
                 u_pm, z_pm, xp):
    """One class-pooled estimator step (module docstring; DESIGN.md Sec 9).

    The decision peer keeps the exact per-peer law: its watch-share death
    count is Poisson-sampled from ``u_pm``/``z_pm[:, 0]`` (the dedicated
    ``_PM_STREAM`` noise) and decayed through the same window-K MLE as a
    per-peer row.  The other k-1 peers are carried as per-class moments fed
    in expectation, plus the population variance ``pm_v`` of their point
    estimates, which evolves by the exchangeable mean-field recurrence

        v' = (beta_bar^2 * v * den_bar^2 + lam_bar) / den_bar'^2

    (numerator noise of each peer's windowed estimate is Poisson with the
    class-mean intensity; denominators are treated at their pooled mean).
    A due gossip round replaces the per-peer ring pull with its moment
    law: every participant's remote mean is a without-replacement sample
    of ``fanout`` of the other k-1 point estimates, so it is distributed
    around the population mean with the exact exchangeability correction
    ``fpc = (N - F) / ((N - 1) * F)``, ``N = k-1``.  The decision peer
    samples that pull (``z_pm[:, 1]``); the class moments re-seed at their
    mean-field merged value and the population variance contracts by
    ``(1-w)^2 + w^2 * fpc``.  Isolated cells never reach the gossip
    branch and are exact in this form.

    Returns the decision row (ema_d0, ema_T0, mu0_0), the class moments
    (pm_d, pm_T, pm_mu0, pm_v), and the gossip clock (round_inc, next_g)
    for the caller to merge under ``p.pm_on``.
    """
    a = p.prior_count
    share = p.watch / p.k                       # watch slots per peer
    kw = xp.maximum(p.k - 1.0, 1.0)
    nw = p.pm_nc / kw[:, None]                  # class weights over k-1 peers

    # Decision row: sampled, like per-peer slot 0.
    lam0 = (share * p.hmean_peer[:, 0] * mu
            + p.shock_rate * p.shock_dpeer[:, 0]) * elapsed
    d0 = _sample_counts(lam0, u_pm, z_pm[:, 0], xp)
    beta0 = xp.exp(d0 * p.log_decay)
    ema_d0 = s.ema_d[:, 0] * beta0 + d0
    ema_T0 = s.ema_T[:, 0] * beta0 + share * elapsed

    # Class moments: expectation-fed, like the pooled regime per class.
    lam_c = (share[:, None] * p.pm_rate * mu[:, None]
             + p.shock_rate[:, None] * p.pm_shock) * elapsed[:, None]
    beta_c = xp.exp(lam_c * p.log_decay[:, None])
    pm_d = s.pm_d * beta_c + lam_c
    pm_T = s.pm_T * beta_c + share[:, None] * elapsed[:, None]

    # Population-variance recurrence (denominators at their pooled mean).
    den_old = xp.sum(nw * (s.pm_T + a[:, None] / s.pm_mu0), axis=-1)
    den_new = xp.sum(nw * (pm_T + a[:, None] / s.pm_mu0), axis=-1)
    lam_bar = xp.sum(nw * lam_c, axis=-1)
    beta_bar = xp.sum(nw * beta_c, axis=-1)
    pm_v = ((beta_bar ** 2 * s.pm_v * den_old ** 2 + lam_bar)
            / xp.maximum(den_new, 1e-300) ** 2)

    # Gossip round (mean-field ring pull with the fpc correction).
    due = ((p.regime == _REGIME_IDS["gossip"]) & ~finished & (t >= s.next_g)
           & p.pm_on)
    mu_hat0 = (ema_d0 + a) / (ema_T0 + a / s.mu0[:, 0])
    mu_c = (pm_d + a[:, None]) / (pm_T + a[:, None] / s.pm_mu0)
    mbar = xp.sum(nw * mu_c, axis=-1)           # mean of the k-1 others
    N = kw
    fpc = (xp.maximum(N - p.g_fanout, 0.0)
           / (xp.maximum(N - 1.0, 1.0) * p.g_fanout))
    w = p.g_weight
    rem0 = mbar + z_pm[:, 1] * xp.sqrt(xp.maximum(pm_v, 0.0) * fpc)
    merged0 = (1.0 - w) * mu_hat0 + w * xp.maximum(rem0, 1e-300)
    # A pooled peer's remote pool includes the decision peer (1/N of it).
    mall = (mu_hat0 + (p.k - 1.0) * mbar) / xp.maximum(p.k, 1.0)
    merged_c = (1.0 - w)[:, None] * mu_c + (w * mall)[:, None]
    contract = (1.0 - w) ** 2 + w ** 2 * fpc

    ema_d0 = xp.where(due, 0.0, ema_d0)
    ema_T0 = xp.where(due, 0.0, ema_T0)
    mu0_0 = xp.where(due, merged0, s.mu0[:, 0])
    pm_d = xp.where(due[:, None], 0.0, pm_d)
    pm_T = xp.where(due[:, None], 0.0, pm_T)
    pm_mu0 = xp.where(due[:, None], merged_c, s.pm_mu0)
    pm_v = xp.where(due, contract * pm_v, pm_v)
    next_g = xp.where(due, t + p.g_period, s.next_g)
    return (ema_d0, ema_T0, mu0_0, pm_d, pm_T, pm_mu0, pm_v,
            due * 1.0, next_g)


def _apply(s: _State, p: _Params, pre, u, z, u3, z3, u_pm, z_pm,
           macro_threshold, peer_axis: int, any_pm: bool, xp) -> _State:
    """Pure post-sampling half: advance each cell by one (macro-)attempt.

    ``u`` is a uniform draw (failure time for regular cells, geometric
    failure count for macro cells); ``z`` a standard normal (macro burst
    duration).  ``u3``/``z3`` (shape [B, peer_axis], or None when
    ``peer_axis`` is 1) drive the per-peer observation sampling of
    non-pooled estimator regimes.  ``u_pm``/``z_pm`` ([B] / [B, 2], None
    unless ``any_pm``) drive the class-pooled form's decision-row and
    gossip-pull noise from the dedicated ``_PM_STREAM`` stream.
    """
    (mu, kmu, attempt_len, work_target, is_final, cycle_len, censor_now, att,
     td_rest, from_server) = pre
    p_surv = xp.exp(-kmu * cycle_len)

    # ---------------- macro path: a whole failure burst ------------------ #
    # Failures before the next completed cycle ~ Geometric(p_surv); each
    # failure costs a truncated-exp attempt plus a geometric number of
    # restore tries.  Means/variances are exact; the burst duration is
    # their CLT normal.  The jump is capped by the hazard coherence time
    # (and the censor horizon) so mu(t) stays locally valid.
    r = xp.exp(-kmu * p.T_d)                       # restore attempt succeeds
    m_a, v_a = _trunc_exp_moments(kmu, cycle_len, p_surv, xp)
    m_r, v_r = _trunc_exp_moments(kmu, p.T_d, r, xp)
    retries = 1.0 / xp.maximum(r, 1e-300) - 1.0    # mean failed restore tries
    mean_restore = p.T_d + retries * m_r
    var_restore = retries * v_r + (retries / xp.maximum(r, 1e-300)) * m_r * m_r
    pair_m = m_a + mean_restore                    # one failure+recovery
    pair_v = v_a + v_r + var_restore
    M_want = xp.floor(xp.log(xp.maximum(u, 1e-300))
                      / xp.minimum(xp.log1p(-p_surv), -1e-300))
    horizon = xp.minimum(_coherence(s.t, p, xp),
                         0.5 * (p.t0 + p.max_wall - s.t) + pair_m)
    # Adaptive cells must not macro-step past their own learning: the
    # estimator only updates BETWEEN steps, so a burst is capped at about
    # one window turnover of watch-neighbourhood deaths (window/(watch*mu)
    # seconds) — the same timescale on which the exact path escapes a
    # mis-estimated livelock.  Fixed and oracle cells have nothing to
    # learn and keep the full burst.
    horizon = xp.minimum(horizon, xp.where(
        p.pol == 1, p.window / xp.maximum(p.hsum_watch * mu, 1e-300), xp.inf))
    M_cap = xp.floor(horizon / xp.maximum(pair_m, 1e-300))
    M = xp.clip(xp.minimum(M_want, M_cap), 0.0, _MACRO_CAP)
    # Store cells never macro-step: the burst closed form above assumes a
    # constant per-failure restore time, which endogenous T_d is not.
    # Shocked cells never macro-step either (DESIGN.md Sec 8): a burst
    # must not straddle a shock epoch — the adaptive burst cap
    # window/(watch*mu) above counts only background deaths, so an epoch
    # inside the burst would outrun the estimator exactly like a
    # mis-estimated livelock; ~p.shocked is all-True for unshocked
    # batches, keeping them bit-identical.
    macro = (att & ~s.in_restore & ~p.store_on & ~p.shocked
             & (p_surv < macro_threshold)
             & xp.isfinite(kmu) & (kmu > 0.0) & (M >= 1.0))
    capped = macro & (M < M_want)
    m_ok = macro & ~capped                         # burst ends in a success
    burst = xp.maximum(M * pair_m + z * xp.sqrt(M * pair_v), 0.0)
    burst_waste = xp.minimum(M * m_a, burst)

    # ---------------- regular path: one attempt, exact ------------------- #
    # (Cells whose macro cap rounded to zero step exactly this round.)
    reg = att & ~macro
    t_fail = -xp.log1p(-u) / kmu
    fail = t_fail < attempt_len
    dt = xp.where(reg, xp.minimum(t_fail, attempt_len), 0.0)
    ws = reg & ~s.in_restore & ~fail   # work cycle completed
    wf = reg & ~s.in_restore & fail    # work cycle lost to churn
    rs = reg & s.in_restore & ~fail    # restore (image download) completed
    rf = reg & s.in_restore & fail     # restore attempt lost to churn
    interior = (ws | m_ok) & ~is_final             # completed cycle, checkpoints

    t = s.t + xp.where(ws, cycle_len,
             xp.where(wf | rf, dt,
             xp.where(rs, td_rest,
             xp.where(macro, burst + xp.where(m_ok, cycle_len, 0.0), 0.0))))
    done = xp.where(ws | m_ok,
                    xp.where(is_final, p.work, s.done + work_target), s.done)
    n_ckpt = s.n_ckpt + interior
    ckpt_time = s.ckpt_time + xp.where(interior, p.V, 0.0)
    n_fail = s.n_fail + wf + xp.where(macro, M, 0.0)
    wasted = s.wasted + xp.where(wf, dt, 0.0) + xp.where(macro, burst_waste, 0.0)
    restore_time = (s.restore_time + xp.where(rf, dt, xp.where(rs, td_rest, 0.0))
                    + xp.where(macro, burst - burst_waste, 0.0))
    in_restore = (s.in_restore | wf) & ~rs
    finished = s.finished | censor_now | ((ws | m_ok) & is_final)
    censored = s.censored | censor_now
    seen_ckpt = s.seen_ckpt | interior
    seen_restore = s.seen_restore | rs | m_ok | capped
    # All k peers experience a completed restore (the job stalls together),
    # so every peer slot observes its duration — mirror of observe_restore.
    td_obs = xp.where(rs[:, None], td_rest[:, None], s.td_obs)
    # Server I/O accounting, billed per ATTEMPT: server-only cells (R=0)
    # upload every interior checkpoint; any store-cell restore attempt that
    # found no surviving replica pulls from the server fallback — including
    # churn-interrupted attempts, which still moved dt/td of the image
    # through the shared pipe before dying (the undercount would otherwise
    # be worst exactly under heavy churn).
    srv_ckpt = interior & p.store_on & (p.R < 1.0)
    srv_rest = rs & from_server  # exclusive with srv_ckpt (work vs restore)
    srv_part = rf & from_server  # interrupted server download (partial)
    frac = xp.where(srv_part, dt / xp.maximum(td_rest, 1e-300), 0.0)
    sv_bytes = (s.sv_bytes + xp.where(srv_ckpt | srv_rest, p.img_bytes, 0.0)
                + frac * p.img_bytes)
    n_srv = s.n_srv + srv_rest
    n_peer = s.n_peer + (rs & p.store_on & ~from_server)

    # Estimator: deaths among the watch neighbourhood over the elapsed
    # time, decayed through the window-K MLE (Eq. 1, exposure form).
    # Pooled cells feed the whole neighbourhood's stream in expectation to
    # peer slot 0; isolated/gossip cells Poisson-sample each peer's 1/k
    # share (sampling noise IS the fidelity axis being modelled).
    elapsed = t - s.t
    if peer_axis == 1:
        # Deaths arrive at the class-weighted watch rate (hsum_watch ==
        # float(watch) for homogeneous cells) plus the correlated-shock
        # death rate among the watched scope (rate * f * n_scope_watch,
        # exactly +0.0 when unshocked); exposure stays in raw
        # slot-seconds — the estimator is class-blind, like the heap MLE,
        # and therefore converges to the watch-pool mean EFFECTIVE hazard
        # including shocks, which is what the interval rule should see.
        d = ((p.hsum_watch * mu + p.shock_rate * p.shock_dwatch)
             * elapsed)[:, None]
        expo = (p.watch * elapsed)[:, None]
        beta = xp.exp(d * p.log_decay[:, None])
        ema_d = s.ema_d * beta + d
        ema_T = s.ema_T * beta + expo
        mu0, n_round, next_g = s.mu0, s.n_round, s.next_g
    else:
        pooled = p.regime == _REGIME_IDS["pooled"]
        peer_act = (xp.arange(peer_axis)[None, :]
                    < xp.where(pooled, 1.0, p.k)[:, None])
        rate_slot = xp.where(pooled, p.watch, p.watch / p.k)  # slots per peer
        # Death intensity per peer: its watch/k slot share scaled by the
        # mean class multiplier of that share (all 1.0 when homogeneous),
        # plus its share of the shock-death intensity (exact in-scope
        # count of the j::k slot share; +0.0 when unshocked).  Epoch-level
        # burst clustering within one step is folded into the per-step
        # Poisson draw — mean-exact; the heap oracle delivers the true
        # simultaneous bursts and the parity suite bounds the difference.
        rate_death = xp.where(pooled[:, None], p.hsum_watch[:, None],
                              (p.watch / p.k)[:, None]
                              * p.hmean_peer[:, :peer_axis])
        lam = (rate_death * (mu * elapsed)[:, None]
               + (p.shock_rate * elapsed)[:, None]
               * p.shock_dpeer[:, :peer_axis]) * peer_act
        d = xp.where(pooled[:, None], lam, _sample_counts(lam, u3, z3, xp))
        beta = xp.exp(d * p.log_decay[:, None])
        ema_d = xp.where(peer_act, s.ema_d * beta + d, s.ema_d)
        ema_T = xp.where(peer_act,
                         s.ema_T * beta + rate_slot[:, None]
                         * elapsed[:, None], s.ema_T)
        ema_d, ema_T, mu0, n_round, next_g = _gossip_mix(
            t, ema_d, ema_T, s.mu0, s.n_round, s.next_g, finished,
            peer_act, p, xp)

    # Class-pooled cells override whatever the branch above wrote to their
    # decision row and gossip clock — their noise comes from the dedicated
    # _PM_STREAM draws, so the realization is identical whichever branch
    # the batch composition put them through.
    pm_d, pm_T, pm_mu0, pm_v = s.pm_d, s.pm_T, s.pm_mu0, s.pm_v
    if any_pm:
        (ema_d0, ema_T0, mu0_0, pmd, pmT, pmm, pmv, rinc, next_g_pm) = \
            _pool_update(s, p, t, elapsed, mu, finished, u_pm, z_pm, xp)
        col0 = p.pm_on[:, None] & (xp.arange(ema_d.shape[1])[None, :] == 0)
        ema_d = xp.where(col0, ema_d0[:, None], ema_d)
        ema_T = xp.where(col0, ema_T0[:, None], ema_T)
        mu0 = xp.where(col0, mu0_0[:, None], mu0)
        pm_d = xp.where(p.pm_on[:, None], pmd, pm_d)
        pm_T = xp.where(p.pm_on[:, None], pmT, pm_T)
        pm_mu0 = xp.where(p.pm_on[:, None], pmm, pm_mu0)
        pm_v = xp.where(p.pm_on, pmv, pm_v)
        n_round = xp.where(p.pm_on, s.n_round + rinc, n_round)
        next_g = xp.where(p.pm_on, next_g_pm, next_g)

    return _State(t=t, done=done, in_restore=in_restore, finished=finished,
                  censored=censored, n_ckpt=n_ckpt, n_fail=n_fail,
                  wasted=wasted, ckpt_time=ckpt_time, restore_time=restore_time,
                  ema_d=ema_d, ema_T=ema_T, mu0=mu0, seen_ckpt=seen_ckpt,
                  seen_restore=seen_restore, td_obs=td_obs, next_g=next_g,
                  n_round=n_round, sv_bytes=sv_bytes,
                  n_srv=n_srv, n_peer=n_peer,
                  pm_d=pm_d, pm_T=pm_T, pm_mu0=pm_mu0, pm_v=pm_v)


# --------------------------------------------------------------------------- #
# NumPy backend.                                                               #
# --------------------------------------------------------------------------- #

def _lw_numpy(z):
    return lambertw0_numpy(z, iters=_LW_ITERS)


def _run_numpy(p: _Params, seeds: Sequence[int], max_steps: int,
               macro_threshold: float, any_store: bool, any_het: bool,
               any_shock: bool, any_pm: bool, peer_axis: int) -> tuple:
    # One stream per UNIQUE seed, consumed positionally (draw i belongs to
    # step i): a cell's realization depends only on its own seed, never on
    # batch composition, and cells sharing a seed share churn randomness —
    # common random numbers across the policies of a comparison, like the
    # reference engine's seed reuse.  Per-peer observation noise (non-pooled
    # estimator regimes) comes from a SECOND stream per seed, tagged
    # _OBS_STREAM, so pooled-only batches draw exactly what they always did
    # and a regime cell's noise is likewise composition-invariant (the peer
    # axis is the fixed _PEER_CAP, never the batch max).
    uniq, inv = np.unique(np.asarray(list(seeds), dtype=np.int64),
                          return_inverse=True)
    gens = [np.random.default_rng(int(sd)) for sd in uniq]
    obs_gens = ([np.random.default_rng(np.random.SeedSequence(
        [int(sd), _OBS_STREAM])) for sd in uniq] if peer_axis > 1 else None)
    # Third stream per seed: class-pooled decision-row + gossip-pull noise.
    pm_gens = ([np.random.default_rng(np.random.SeedSequence(
        [int(sd), _PM_STREAM])) for sd in uniq] if any_pm else None)
    s = _init_state(p, np, peer_axis)
    steps = 0
    block_u = block_z = block_u2 = block_u3 = block_z3 = None
    block_upm = block_zpm = None
    u3 = z3 = u_pm = z_pm = None
    j = _RNG_BLOCK
    # Unused branches of the branchless step routinely overflow (exp of a
    # huge rate, inf * 0) before being masked out — silence numpy there.
    with np.errstate(all="ignore"):
        while steps < max_steps and not s.finished.all():
            if j == _RNG_BLOCK:  # refill per-seed blocks
                block_u = np.stack([g.random(_RNG_BLOCK) for g in gens])
                block_z = np.stack([g.standard_normal(_RNG_BLOCK) for g in gens])
                block_u2 = np.stack([g.random(_RNG_BLOCK) for g in gens])
                if obs_gens is not None:
                    block_u3 = np.stack([g.random((peer_axis, _RNG_BLOCK))
                                         for g in obs_gens])
                    block_z3 = np.stack([g.standard_normal(
                        (peer_axis, _RNG_BLOCK)) for g in obs_gens])
                if pm_gens is not None:
                    block_upm = np.stack([g.random(_RNG_BLOCK)
                                          for g in pm_gens])
                    block_zpm = np.stack([g.standard_normal((2, _RNG_BLOCK))
                                          for g in pm_gens])
                j = 0
            steps += 1
            u = block_u[inv, j]
            z = block_z[inv, j]
            u2 = block_u2[inv, j]
            if obs_gens is not None:
                u3 = block_u3[inv, :, j]
                z3 = block_z3[inv, :, j]
            if pm_gens is not None:
                u_pm = block_upm[inv, j]
                z_pm = block_zpm[inv, :, j]
            j += 1
            pre = _attempt(s, p, u2, np, _lw_numpy, any_store, any_het,
                           any_shock)
            s = _apply(s, p, pre, u, z, u3, z3, u_pm, z_pm, macro_threshold,
                       peer_axis, any_pm, np)
    return s, steps


# --------------------------------------------------------------------------- #
# JAX backend: lax.scan over attempt steps, chunked for early exit.            #
# --------------------------------------------------------------------------- #

if _HAVE_JAX:

    def lambertw0_jnp(z):
        from repro.core.lambertw import lambertw0

        return lambertw0(z, iters=_LW_ITERS)

    def _step_draws(keys, peer_axis: int, any_pm: bool):
        """One step's noise draws from the per-cell key chain.

        Per-CELL keys (seeded from CellSpec.seed): realizations are
        independent of batch composition, and same-seed cells share churn
        randomness (common random numbers across policies).  Always split
        6-way — keys are stateless, so the unused observation-noise keys
        of pooled batches cost nothing and the split count never depends
        on batch composition.  Class-pooled noise folds ``_PM_STREAM``
        into the observation keys, so it is independent of the per-peer
        draws AND invariant to whether the batch materialized them.
        """
        splits = jax.vmap(lambda k: jax.random.split(k, 6))(keys)
        keys, k1, k2, k3, k4, k5 = (splits[:, 0], splits[:, 1],
                                    splits[:, 2], splits[:, 3],
                                    splits[:, 4], splits[:, 5])
        u = jax.vmap(lambda k: jax.random.uniform(k, dtype=jnp.float64))(k1)
        z = jax.vmap(lambda k: jax.random.normal(k, dtype=jnp.float64))(k2)
        u2 = jax.vmap(lambda k: jax.random.uniform(k, dtype=jnp.float64))(k3)
        if peer_axis > 1:
            u3 = jax.vmap(lambda k: jax.random.uniform(
                k, (peer_axis,), dtype=jnp.float64))(k4)
            z3 = jax.vmap(lambda k: jax.random.normal(
                k, (peer_axis,), dtype=jnp.float64))(k5)
        else:
            u3 = z3 = None
        if any_pm:
            u_pm = jax.vmap(lambda k: jax.random.uniform(
                jax.random.fold_in(k, _PM_STREAM), dtype=jnp.float64))(k4)
            z_pm = jax.vmap(lambda k: jax.random.normal(
                jax.random.fold_in(k, _PM_STREAM), (2,),
                dtype=jnp.float64))(k5)
        else:
            u_pm = z_pm = None
        return keys, u, z, u2, u3, z3, u_pm, z_pm

    def _jax_chunk(state_and_keys, p: _Params, macro_threshold: float,
                   any_store: bool, any_het: bool, any_shock: bool,
                   any_pm: bool, peer_axis: int, chunk: int):
        def body(carry, _):
            s, keys = carry
            keys, u, z, u2, u3, z3, u_pm, z_pm = _step_draws(
                keys, peer_axis, any_pm)
            pre = _attempt(s, p, u2, jnp, lambertw0_jnp, any_store, any_het,
                           any_shock)
            return (_apply(s, p, pre, u, z, u3, z3, u_pm, z_pm,
                           macro_threshold, peer_axis, any_pm, jnp),
                    keys), None

        (s, keys), _ = jax.lax.scan(body, state_and_keys, None, length=chunk)
        return s, keys

    def engine_chunk(state_and_keys, p: _Params, macro_threshold: float,
                     any_store: bool, any_het: bool, any_shock: bool,
                     any_pm: bool, peer_axis: int, chunk: int):
        """``_jax_chunk``, jitted under a stable program name: its runs
        show as ``jit_engine_chunk`` in a profile."""
        return _jax_chunk(state_and_keys, p, macro_threshold, any_store,
                          any_het, any_shock, any_pm, peer_axis, chunk)

    _jax_chunk_jit = None  # compiled lazily (needs x64 enabled at trace time)
    _SHARDED_CACHE: dict = {}  # (mesh, statics...) -> jitted shard_map chunk

    def _get_sharded_chunk(mesh, axes, macro_threshold, any_store, any_het,
                           any_shock, any_pm, peer_axis, chunk, tmpl):
        """Jitted shard_map'd chunk for a (mesh, statics) combination.

        Cells are independent, so the per-shard program is the unmodified
        chunk body; the only collective is the psum that hands the host a
        replicated global unfinished count, keeping the early-exit check
        from gathering the sharded state.
        """
        key = (mesh, axes, macro_threshold, any_store, any_het, any_shock,
               any_pm, peer_axis, chunk)
        fn = _SHARDED_CACHE.get(key)
        if fn is not None:
            return fn
        from jax.sharding import PartitionSpec as P

        def engine_chunk(s, keys, pj):  # the program: jit_engine_chunk
            s, keys = _jax_chunk((s, keys), pj, macro_threshold, any_store,
                                 any_het, any_shock, any_pm, peer_axis, chunk)
            unfin = jax.lax.psum(
                jnp.sum((~s.finished).astype(jnp.int32)), axes)
            return s, keys, unfin

        lead = lambda x: P(tuple(axes), *([None] * (np.ndim(x) - 1)))
        s_tmpl, k_tmpl, p_tmpl = tmpl
        in_specs = (jax.tree.map(lead, s_tmpl), lead(k_tmpl),
                    jax.tree.map(lead, p_tmpl))
        out_specs = (jax.tree.map(lead, s_tmpl), lead(k_tmpl), P())
        fn = jax.jit(jax.shard_map(engine_chunk, mesh=mesh, in_specs=in_specs,
                                   out_specs=out_specs, check_vma=False))
        _SHARDED_CACHE[key] = fn
        return fn


def _run_jax(p: _Params, seeds: Sequence[int], max_steps: int,
             macro_threshold: float, any_store: bool, any_het: bool,
             any_shock: bool, any_pm: bool, peer_axis: int, chunk: int,
             mesh, step: str) -> tuple:
    global _jax_chunk_jit
    with jax.enable_x64(True):
        B = len(seeds)
        seeds = list(seeds)
        axes = None
        if mesh is not None and step != "fused":
            # Resolve the "cell" logical axis against the mesh's data axes
            # (distributed/sharding.py priority list).  The batch is padded
            # to the data extent by replicating the last cell; padding is
            # born finished, so it costs one no-op lane per chunk and is
            # sliced off the result.
            from repro.distributed.sharding import resolve_rules

            n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
            Bp = -(-B // max(n_dev, 1)) * max(n_dev, 1)
            axes = resolve_rules(mesh, {"cell": Bp}).physical("cell")
            if axes is not None and B != Bp:
                pad = Bp - B
                p = _Params(*(np.concatenate(
                    [a, np.repeat(a[-1:], pad, axis=0)]) for a in p))
                seeds = seeds + [seeds[-1]] * pad
        if _jax_chunk_jit is None:
            _jax_chunk_jit = jax.jit(engine_chunk,
                                     static_argnums=(2, 3, 4, 5, 6, 7, 8))
        # Host time to dispatch the transfers and the initial state: the
        # copies are asynchronous and finish inside the first chunk or sync.
        with tracing.span("sim.upload"):
            pj = _Params(*(jnp.asarray(a) for a in p))
            keys = jax.vmap(jax.random.PRNGKey)(
                jnp.asarray(list(seeds), dtype=jnp.uint32))
            s = _init_state(pj, jnp, peer_axis)
            if len(seeds) != B:
                s = s._replace(finished=s.finished
                               | (jnp.arange(len(seeds)) >= B))
        if step == "fused":
            from repro.kernels.sim_step import fused_chunk

            def advance(s, keys):
                s, keys = fused_chunk(
                    s, keys, pj, macro_threshold=macro_threshold,
                    any_store=any_store, any_het=any_het,
                    any_shock=any_shock, any_pm=any_pm, chunk=chunk)
                return s, keys, lambda: not bool(s.finished.all())
        elif axes is not None:
            fn = _get_sharded_chunk(mesh, axes, macro_threshold, any_store,
                                    any_het, any_shock, any_pm, peer_axis,
                                    chunk, (s, keys, pj))

            def advance(s, keys):
                s, keys, unfin = fn(s, keys, pj)
                return s, keys, lambda: int(unfin) != 0
        else:
            def advance(s, keys):
                s, keys = _jax_chunk_jit((s, keys), pj, macro_threshold,
                                         any_store, any_het, any_shock,
                                         any_pm, peer_axis, chunk)
                return s, keys, lambda: not bool(s.finished.all())
        steps = 0
        while steps < max_steps:
            with tracing.span("sim.chunk"):
                s, keys, unfinished = advance(s, keys)
            steps += chunk
            # The early-exit check waits for the chunk to finish on the
            # device and reads one number back.
            with tracing.span("sim.sync"):
                running = unfinished()
            tracing.count("sim.host_syncs")
            if not running:
                break
        with tracing.span("sim.download"):
            return _State(*(np.asarray(a)[:B] for a in s)), steps


# --------------------------------------------------------------------------- #
# Public entry point.                                                          #
# --------------------------------------------------------------------------- #

def run_cells(cells: Sequence[CellSpec], *, backend: str = "auto",
              max_steps: int = 400_000, macro_threshold: float = 0.05,
              peer_form: str = "auto", chunk: Optional[int] = None,
              mesh="auto", step: str = "auto") -> BatchResult:
    """Simulate every cell to completion (or censoring) and return a batch.

    ``backend``: "auto" (the ``REPRO_SIM_BACKEND`` env var when set, else
    JAX when importable, else numpy), "jax", "numpy".
    ``max_steps`` bounds the attempt loop; cells still running when it is
    exhausted are reported censored at their current wall clock.
    ``macro_threshold``: cycle survival probability below which failure
    bursts are macro-stepped (see module docstring); 0 disables.  Cells
    with a :class:`repro.p2p.StoreSpec` never macro-step (endogenous T_d).
    ``peer_form``: which form carries non-pooled estimator state (module
    docstring) — "auto" (per-peer rows up to k = ``_PEER_CAP``,
    class-pooled moments beyond), "perpeer" (historical hard cap), "pm"
    (force class-pooled at any k — the parity suite's knob).
    ``chunk``: engine steps per jitted call on the JAX backend (defaults
    to ``REPRO_SIM_CHUNK`` or :data:`DEFAULT_CHUNK`).
    ``mesh``: cell-batch sharding on the JAX backend — "auto" (shard over
    a 1-D data mesh of all local devices when more than one is present),
    ``None`` (single device), or an explicit :class:`jax.sharding.Mesh`
    whose data axes the ``cell`` logical axis is resolved against.
    ``step``: inner-step implementation on the JAX backend — "auto"
    (``REPRO_SIM_STEP`` env var, else "scan"), "scan" (stock ``lax.scan``
    body), "fused" (the Pallas kernel of :mod:`repro.kernels.sim_step`;
    requires a batch with no per-peer-form cells, runs unsharded, and
    runs only in Pallas interpret mode on the CPU backend: elsewhere it
    raises ``NotImplementedError`` naming the compiler's refusal).
    """
    if backend == "auto":
        backend = os.environ.get("REPRO_SIM_BACKEND") or (
            "jax" if _HAVE_JAX else "numpy")
    if backend == "jax" and not _HAVE_JAX:
        raise RuntimeError("JAX backend requested but jax is not importable")
    if backend not in ("jax", "numpy"):
        raise ValueError(f"unknown backend {backend!r}")
    if step == "auto":
        step = os.environ.get("REPRO_SIM_STEP") or "scan"
    if step not in ("scan", "fused"):
        raise ValueError(f"unknown step {step!r}")
    if chunk is None:
        chunk = int(os.environ.get("REPRO_SIM_CHUNK") or DEFAULT_CHUNK)
    chunk = int(chunk)
    if chunk < 1:
        raise ValueError("chunk must be >= 1")

    # Calls are counted, so that other counters can be read per call; the
    # running count is also this call's span id.
    with tracing.span("sim.run_cells", id=tracing.count("sim.run_cells")):
        with tracing.span("sim.pack"):
            p = _pack(cells, peer_form)
        seeds = [c.seed for c in cells]
        any_store = any(c.store is not None for c in cells)
        any_het = bool(p.store_mix.any())
        any_shock = any(_cell_shock(c) is not None for c in cells)
        any_pm = bool(p.pm_on.any())
        # Per-peer estimator state is only materialized when some cell needs
        # it (class-pooled cells keep their decision row in slot 0 of a
        # width-1 axis, so an all-pm batch stays narrow at any k).
        peer_axis = (_PEER_CAP if any(
            c.policy.regime != "pooled" and not pm
            for c, pm in zip(cells, p.pm_on)) else 1)
        if step == "fused":
            if backend != "jax":
                raise ValueError("step='fused' requires the JAX backend")
            if peer_axis != 1:
                raise ValueError(
                    "step='fused' supports batches with no per-peer-form "
                    "cells (pooled or class-pooled estimators only)")
        if backend == "jax":
            mesh_obj = None
            if mesh == "auto":
                if len(jax.devices()) > 1:
                    from repro.distributed.mesh import cell_mesh
                    mesh_obj = cell_mesh()
            elif mesh is not None:
                mesh_obj = mesh
            s, steps = _run_jax(p, seeds, max_steps, float(macro_threshold),
                                any_store, any_het, any_shock, any_pm,
                                peer_axis, chunk, mesh_obj, step)
        else:
            s, steps = _run_numpy(p, seeds, max_steps, float(macro_threshold),
                                  any_store, any_het, any_shock, any_pm,
                                  peer_axis)

        ran_out = ~np.asarray(s.finished)
        completed = ~(np.asarray(s.censored) | ran_out)
        return BatchResult(
            wall_time=np.asarray(s.t) - p.t0,
            work_required=p.work / p.speed,
            n_checkpoints=np.asarray(s.n_ckpt).astype(np.int64),
            n_failures=np.asarray(s.n_fail).astype(np.int64),
            wasted_work=np.asarray(s.wasted),
            checkpoint_time=np.asarray(s.ckpt_time),
            restore_time=np.asarray(s.restore_time),
            completed=completed,
            server_bytes=np.asarray(s.sv_bytes),
            n_server_restores=np.asarray(s.n_srv).astype(np.int64),
            n_peer_restores=np.asarray(s.n_peer).astype(np.int64),
            n_steps=steps,
        )
