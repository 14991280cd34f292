"""The fault-tolerant trainer under the benchmark: FaultTolerantTrainer.run.

Set-up builds one ``FaultTolerantTrainer`` with its ``AsyncCheckpointer``
from the configuration file, with weights and data drawn from ``--seed``,
and calls ``run`` once.  The benchmark stands between the trainer and its
attributes (``train_step``, ``ckpt.save``, ``ckpt.wait``,
``data.batch_at``) to time them, without a change to the program:

* the first ``warm_steps`` calls of the step are set-up.  They compile (or
  load) the step, and their losses, the first gradient as the optimizer
  holds it (its first moment after one step) and the parameters' change
  after them are read for the check against the reference;
* the window opens at the next call and closes at the first call after
  ``--seconds``, which then stops ``run``.  Every step and save between is
  in the window, whole;
* after each save, the state that was saved is fingerprinted on the chip
  before the next step consumes it; after the window the committed image is
  read back from disk by a plain reader and must give the same
  fingerprints.

Then the program's state is freed and the float32 reference
(``bench/reference/olmo.py``) runs the same steps from the same seed.
"""
from __future__ import annotations

import gc
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

from bench import registry, seeds
from bench.compare import check, max_rel_gap, worst_leaf_gap
from bench.harness import Outcome
from bench.reference import ckpt_image, olmo

SEED_TAG = 0x6F6C6D6F   # "olmo"
CKPT_DIR = registry.BENCH_DIR / ".ckpt"


class WindowClosed(Exception):
    """Raised from the step to end ``FaultTolerantTrainer.run``."""


def model_config(cfg: Dict[str, Any]):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import AttentionConfig, ModelConfig, RopeConfig

    run = cfg["run"]
    heads = int(cfg["num_attention_heads"])
    if cfg["hidden_act"] != "silu" or cfg["layer_norm"] != "non-parametric":
        raise ValueError("this module runs OLMo blocks: SwiGLU, "
                         "non-parametric LayerNorm")
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=int(cfg["num_hidden_layers"]), d_model=int(cfg["hidden_size"]),
        d_ff=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
        attention=AttentionConfig(
            n_heads=heads, n_kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["hidden_size"]) // heads,
            rope=RopeConfig(theta=float(cfg["rope_theta"]))),
        norm="nonparametric", norm_eps=float(cfg["layer_norm_eps"]),
        act="silu_gated", tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=run["param_dtype"], compute_dtype=run["compute_dtype"],
        remat=run["remat"])


def leaf_name(path) -> str:
    """The last key of a pytree path: ``tok``, ``wq``, ``w_up``, ..."""
    k = path[-1]
    return str(getattr(k, "key", getattr(k, "name", k)))


def _named_norms(tree, scale: float = 1.0) -> Dict[str, float]:
    """Per-leaf float32 norms of a parameter-shaped tree, on the chip."""
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return {leaf_name(p): float(n) * scale for (p, _), n in zip(flat, norms)}


def _change_norms(master, initial_host) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(master)[0]
    init_flat = jax.tree.leaves(initial_host)
    norms = jax.jit(lambda xs, ys: [jnp.sqrt(jnp.sum(jnp.square(
        x - y.astype(jnp.float32)))) for x, y in zip(xs, ys)])(
        [x for _, x in flat], [jax.device_put(y) for y in init_flat])
    return {leaf_name(p): float(n) for (p, _), n in zip(flat, norms)}


class StepProbe:
    """Stands in for ``trainer.train_step``: times, reads and stops it."""

    def __init__(self, step_fn, window, rec, warm_steps: int, b1: float,
                 fingerprints: bool):
        self.step_fn, self.window, self.rec = step_fn, window, rec
        self.warm = warm_steps
        self.b1 = b1
        self.calls = 0
        self.timed_steps = 0
        self.warm_losses: List[float] = []
        self.grad_norms: Dict[str, float] = {}
        self.change_norms: Dict[str, float] = {}
        self.initial_params = None
        self.want_fingerprint: Optional[int] = None   # step of the last save
        self.fingerprints: Dict[int, Any] = {}
        self.fingerprint_fn = ckpt_image.device_fingerprints if fingerprints \
            else None

    def __call__(self, state, batch):
        import jax

        i = self.calls
        self.calls += 1
        if self.want_fingerprint is not None:
            self.fingerprints[self.want_fingerprint] = \
                self.fingerprint_fn(state)
            self.want_fingerprint = None
        if i == 0:
            self.initial_params = jax.device_get(state.params)
            if self.fingerprint_fn is not None:
                self.fingerprint_fn(state)         # compile it in set-up
        if i == self.warm:
            self.window.open()
        elif i > self.warm and not self.window.tick():
            self.window.close()
            raise WindowClosed
        with self.rec.span("step"):
            new_state, metrics = self.step_fn(state, batch)
            jax.block_until_ready(metrics["loss"])
        if i >= self.warm:
            self.timed_steps += 1
            return new_state, metrics
        self.warm_losses.append(float(metrics["loss"]))
        if i == 0:
            # Adam's first moment after one step is (1 - b1) * clipped grad.
            self.grad_norms = _named_norms(new_state.opt.m,
                                           1.0 / (1.0 - self.b1))
        if i == self.warm - 1:
            self.change_norms = _change_norms(new_state.opt.master,
                                              self.initial_params)
            self.initial_params = None
        return new_state, metrics


def _instrument_ckpt(ckpt, rec, probe: StepProbe, stats: Dict[str, List]):
    """Time each save from its call to the return of the wait after it."""
    save, wait = ckpt.save, ckpt.wait
    started: List[float] = []

    def timed_save(step, tree):
        started.append(time.monotonic())
        with rec.span("save"):
            blocking = save(step, tree)
        stats["snapshot_s"].append(ckpt.last_blocking_seconds)
        probe.want_fingerprint = step
        return blocking

    def timed_wait(*a, **kw):
        with rec.span("wait"):
            wait(*a, **kw)
        if started:
            t0 = started.pop()
            stats["stall"].append((t0, time.monotonic() - t0))
            stats["write_s"].append(ckpt.last_write_seconds)

    ckpt.save, ckpt.wait = timed_save, timed_wait


def run(cell, *, seed: int, window, rec, dev) -> Outcome:
    import jax

    from repro.ckpt import AsyncCheckpointer
    from repro.data import DataConfig
    from repro.runtime import CheckpointPolicyConfig, FaultTolerantTrainer
    from repro.train.optimizer import AdamWConfig

    cfg, traffic = cell.config, cell.traffic
    run_cfg = cfg["run"]
    s32 = seeds.derive(seed, SEED_TAG)
    every = traffic.get("save_every_steps")
    data = traffic["data"]
    root = CKPT_DIR / cell.name
    shutil.rmtree(root, ignore_errors=True)
    ckpt = AsyncCheckpointer(str(root), n_shards=int(run_cfg["checkpoint_shards"]))
    opt = AdamWConfig(**run_cfg["optimizer"])
    trainer = FaultTolerantTrainer(
        model_config(cfg),
        DataConfig(vocab=int(cfg["vocab_size"]), seq_len=int(run_cfg["seq_len"]),
                   global_batch=int(run_cfg["global_batch"]), seed=s32,
                   zipf_a=float(data["zipf_a"])),
        ckpt=ckpt, opt_cfg=opt, seed=s32,
        policy=CheckpointPolicyConfig(
            kind="fixed",
            fixed_interval=float(every) if every else float("inf")))
    probe = StepProbe(trainer.train_step, window, rec,
                      warm_steps=int(traffic["warm_steps"]), b1=opt.b1,
                      fingerprints=bool(every))
    trainer.train_step = probe
    stats: Dict[str, List] = {"snapshot_s": [], "write_s": [], "stall": []}
    _instrument_ckpt(ckpt, rec, probe, stats)
    batch_at = trainer.data.batch_at

    def timed_batch(step):
        with rec.span("batch"):
            return batch_at(step)

    trainer.data.batch_at = timed_batch
    try:
        trainer.run(n_steps=2 ** 62)
    except WindowClosed:
        pass
    memory_peak = dev.memory_peak_bytes()
    ckpt.close()
    del trainer
    gc.collect()

    # -- checks, after the program's state is freed -------------------- #
    limits = cfg["limits"]
    t_ref = time.monotonic()
    ref = olmo.train(cfg, data, s32, len(probe.warm_losses))
    t_ref = time.monotonic() - t_ref
    readings = {
        "loss1_rel_gap": max_rel_gap(probe.warm_losses[:1], ref.losses[:1]),
        "grad_leaf_gap": worst_leaf_gap(probe.grad_norms, ref.grad_norms),
        "change_leaf_gap": worst_leaf_gap(probe.change_norms,
                                          ref.change_norms),
    }
    # A number the configuration gives no limit is read, not compared: its
    # control and faults read too close to sound runs to set one (PERF.md).
    checks = {k: check(v, limits[k]) for k, v in readings.items()
              if k in limits}
    t_img = time.monotonic()
    if every:
        checks["image_leaves_differing"] = check(
            ckpt_image.leaves_differing(root, probe.fingerprints), 0)
        checks["saves_in_window_at_least"] = check(
            len(probe.fingerprints), 1, at_most=False)
    shutil.rmtree(root, ignore_errors=True)
    print(f"bench: reference {t_ref:.1f} s, image read-back "
          f"{time.monotonic() - t_img:.1f} s; losses {probe.warm_losses}, "
          f"reference {ref.losses}; reference first-gradient leaf norms "
          f"{ref.grad_norms}; not compared: "
          f"{ {k: v for k, v in readings.items() if k not in limits} }",
          file=sys.stderr)

    t0, t1 = window.t_open, window.t_close
    steps = sorted(rec.durations("step", t0, t1))
    if steps:
        print(f"bench: step spans in the window: n {len(steps)}, median "
              f"{steps[len(steps) // 2]:.4f} s, max {steps[-1]:.4f} s, sum "
              f"{sum(steps):.3f} s of {window.elapsed:.3f} s; batch spans "
              f"{sum(rec.durations('batch', t0, t1)):.3f} s", file=sys.stderr)
    tokens_per_step = int(run_cfg["seq_len"]) * int(run_cfg["global_batch"])
    tokens = probe.timed_steps * tokens_per_step
    stalls = [d for t, d in stats["stall"] if t0 <= t < t1]
    e2e = {"train_tokens_per_s": tokens / window.elapsed,
           "goodput_tokens_per_s": tokens / window.elapsed}
    if stalls:
        e2e["save_stall_s"] = sum(stalls) / len(stalls)
    return Outcome(
        e2e=e2e,
        ctx={"tokens": tokens, "config": cfg,
             "snapshot_s": stats["snapshot_s"], "write_s": stats["write_s"]},
        checks=checks, attempted=probe.timed_steps, failed=0,
        memory_peak_bytes=memory_peak)
