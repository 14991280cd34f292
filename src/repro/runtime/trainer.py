"""Fault-tolerant training loop with the paper's adaptive checkpointing.

This is the integration point of the whole framework: a real JAX training
loop (jitted train_step over the model library) wrapped in

    * the ADAPTIVE CHECKPOINT CONTROLLER (paper Sec 3) deciding *when* to
      checkpoint from online-estimated (mu, V, T_d);
    * an ASYNC sharded checkpointer (ckpt/) providing the *mechanism*;
    * a virtual-clock FAILURE INJECTOR (runtime/failures.py) producing
      exponential churn with the paper's k*mu statistics;
    * restart/rollback on failure: restore params+optimizer+data position
      from the last committed checkpoint (deterministic data stream makes
      the replay exact);
    * ELASTIC downsizing: nodes lost for good shrink the fleet; the
      paper's U>0 feasibility test gates the new size;
    * STRAGGLER exclusion feeding the failure-rate estimator.

Virtual-time accounting mirrors the paper's Fig. 3 timeline so the e2e
benchmark (benchmarks/e2e_adaptive.py) can compare adaptive vs fixed
intervals on a *real* training job, reproducing Eq. 11 end-to-end.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro import tracing
from repro.configs.base import ModelConfig
from repro.core.adaptive import AdaptiveCheckpointController
from repro.ckpt.async_ckpt import AsyncCheckpointer
from repro.data.synthetic import DataConfig, SyntheticLM
from repro.runtime.failures import FailureInjector, SimulatedFailure, StragglerMonitor
from repro.train.optimizer import AdamWConfig
from repro.train.schedule import constant
from repro.train.step import TrainState, init_train_state, make_train_step


@dataclass
class CheckpointPolicyConfig:
    """'adaptive' (the paper) or 'fixed' (the baseline of [16])."""

    kind: str = "adaptive"           # 'adaptive' | 'fixed'
    fixed_interval: float = 600.0    # virtual seconds, for kind='fixed'
    prior_mtbf: float = 4 * 3600.0
    prior_v: float = 10.0
    min_interval: float = 1.0
    max_interval: float = 24 * 3600.0


@dataclass
class TrainerReport:
    steps_completed: int
    virtual_time: float
    n_failures: int
    n_checkpoints: int
    n_restarts: int
    wasted_steps: int
    final_k: int
    losses: List[float]
    controller_interval: float

    @property
    def utilization(self) -> float:
        return (self.steps_completed / max(self.virtual_time, 1e-9))


class FaultTolerantTrainer:
    """Single-process harness with production control flow."""

    def __init__(
        self,
        cfg: ModelConfig,
        data_cfg: DataConfig,
        *,
        ckpt: AsyncCheckpointer,
        injector: Optional[FailureInjector] = None,
        policy: CheckpointPolicyConfig = CheckpointPolicyConfig(),
        opt_cfg: AdamWConfig = AdamWConfig(lr=1e-3),
        n_microbatches: int = 1,
        seed: int = 0,
        virtual_ckpt_overhead: Optional[float] = None,
        virtual_restore_time: Optional[float] = None,
        min_feasible_k: int = 1,
    ):
        self.cfg = cfg
        self.data_cfg = data_cfg
        self.ckpt = ckpt
        self.injector = injector
        self.policy = policy
        self.k = injector.k if injector is not None else 1
        self.min_feasible_k = min_feasible_k
        self.controller = AdaptiveCheckpointController(
            k=self.k, prior_mu=1.0 / policy.prior_mtbf, prior_v=policy.prior_v,
            min_interval=policy.min_interval, max_interval=policy.max_interval)
        self.straggler = StragglerMonitor()
        # Virtual overheads: if not given, REAL measured save/restore times
        # are used (scaled 1:1 into virtual seconds).
        self.virtual_ckpt_overhead = virtual_ckpt_overhead
        self.virtual_restore_time = virtual_restore_time

        self.data = SyntheticLM(data_cfg)
        # The state is donated: the step's new state reuses its buffers, so
        # one copy of params + optimizer state lives on the device, not two
        # (olmo-1b at half depth fits one 16 GB chip only this way).  Saves
        # snapshot to host before the next step consumes the buffers.
        self.train_step = jax.jit(
            make_train_step(cfg, opt_cfg, constant(1.0),
                            n_microbatches=n_microbatches),
            donate_argnums=0)
        self._seed = seed

    # ------------------------------------------------------------------ #
    def _interval(self) -> float:
        if self.policy.kind == "fixed":
            return self.policy.fixed_interval
        return self.controller.checkpoint_interval()

    def _feed_observations(self):
        if self.injector is None:
            return
        for lt in self.injector.drain_observations():
            self.controller.observe_failure(lt)

    # ------------------------------------------------------------------ #
    def run(self, n_steps: int, max_restarts: int = 1000,
            *, resume: bool = False) -> TrainerReport:
        """Train to ``n_steps``.  With ``resume=True`` the loop first
        restores the newest committed checkpoint (primary or any surviving
        replica) and continues from it — the process-death recovery path: a
        killed trainer re-run with ``resume=True`` loses nothing beyond the
        last committed checkpoint (deterministic data stream makes the
        replayed tail exact)."""
        state = init_train_state(jax.random.key(self._seed), self.cfg)
        step = 0
        losses: List[float] = []
        n_fail = n_ckpt = n_restart = wasted = 0
        last_ckpt_vtime = 0.0
        committed_step = 0
        vclock = lambda: (self.injector.virtual_time if self.injector else
                          float(step) * 1.0)
        if resume:
            restored = self.ckpt.restore_latest(state)
            if restored is not None:
                committed_step, state = restored
                step = committed_step
                last_ckpt_vtime = vclock()  # the image just loaded

        while step < n_steps:
            i = step  # the id of this step's spans
            with tracing.span("train.batch", id=i):
                batch = self.data.batch_at(step)
            try:
                with tracing.span("train.step", id=i) as sp:
                    if self.injector is not None:
                        self.injector.advance_step()
                    new_state, metrics = self.train_step(state, batch)
                    jax.block_until_ready(metrics["loss"])
            except SimulatedFailure as f:
                # ---- failure: rollback to last committed checkpoint ----
                n_fail += 1
                self.controller.observe_failure(f.lifetime)
                self._feed_observations()
                with tracing.span("train.restore", id=i) as restore_sp:
                    restored = self.ckpt.restore_latest(state)
                t_d = (self.virtual_restore_time if self.virtual_restore_time
                       is not None else restore_sp.seconds)
                if self.injector is not None:
                    self.injector.advance_seconds(t_d)
                self.controller.observe_restore(t_d)
                if restored is not None:
                    committed_step, state = restored
                wasted += step - committed_step
                step = committed_step
                n_restart += 1
                if n_restart > max_restarts:
                    raise RuntimeError("too many restarts") from f
                # elastic: node permanently gone with p=0.5 → shrink fleet
                rng = np.random.default_rng(n_restart)
                if self.injector is not None and rng.random() < 0.5 and self.k > self.min_feasible_k:
                    self.shrink_fleet(self.k - 1)
                continue

            real_dt = sp.seconds
            state = new_state
            with tracing.span("train.loss", id=i):
                losses.append(float(metrics["loss"]))
            step += 1

            # ---- checkpoint decision (the paper's core loop) -------------
            with tracing.span("train.decide", id=i):
                self.controller.observe_step(real_dt)
                self._feed_observations()
                if self.straggler.observe(host=0, step_seconds=real_dt):
                    # a flagged straggler counts as a departure event
                    self.controller.observe_failure(self.straggler.ema * 10)
                since_last = vclock() - last_ckpt_vtime
                due = (self.controller.should_checkpoint(since_last)
                       if self.policy.kind == "adaptive"
                       else since_last >= self.policy.fixed_interval)
            if due:
                blocking = self.ckpt.save(step, state)
                v = (self.virtual_ckpt_overhead if self.virtual_ckpt_overhead
                     is not None else blocking)
                if self.injector is not None:
                    self.injector.advance_seconds(v)
                self.controller.observe_checkpoint_overhead(v)
                n_ckpt += 1
                last_ckpt_vtime = vclock()
                self.ckpt.wait()  # commit before the next failure window
                committed_step = step

        self.ckpt.wait()
        return TrainerReport(
            steps_completed=step, virtual_time=vclock(), n_failures=n_fail,
            n_checkpoints=n_ckpt, n_restarts=n_restart, wasted_steps=wasted,
            final_k=self.k, losses=losses,
            controller_interval=self._interval())

    # ------------------------------------------------------------------ #
    def shrink_fleet(self, new_k: int, *, rebatch: bool = False) -> None:
        """Elastic downsizing, gated by the paper's U>0 feasibility test.

        With ``rebatch=True`` the global batch is scaled with the fleet
        (constant per-node batch): the data pipeline is rebuilt and the
        next train_step call re-specializes on the new shapes (jit cache
        miss == the re-mesh recompile a real elastic runtime performs).
        """
        if new_k < self.min_feasible_k:
            return
        if not self.controller.feasible(new_k):
            # paper Sec 3.2.3: U==0 at this size — refuse to run, keep
            # waiting for replacements instead of livelocking.
            return
        old_k = self.k
        self.k = new_k
        self.controller.k = new_k
        self.controller._invalidate()
        if self.injector is not None:
            self.injector.k = new_k
        if rebatch and new_k != old_k:
            new_batch = max(round(self.data_cfg.global_batch * new_k / old_k), 1)
            if new_batch != self.data_cfg.global_batch:
                import dataclasses
                self.data_cfg = dataclasses.replace(
                    self.data_cfg, global_batch=new_batch)
                self.data = SyntheticLM(self.data_cfg)
