"""``correct`` on the CPU at a size a test run holds: sound runs pass, the
controls fail their limits, and each fault planted under the timed path
turns ``correct`` false.  Everything but the look for a chip runs as on
the chip: the system module, its window, its checks and the result line."""
import dataclasses
import json
import time

import numpy as np
import pytest

from bench import device, faults, harness, registry, seeds
from bench.reference import olmo

SEED = 2 ** 33 + 12345
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            intermediate_size=256, vocab_size=256, num_hidden_layers=2)
# Limits for the tiny size, between its sound readings (loss 2.1e-5 to
# 2.9e-5, leaf gaps up to 1.0e-3 and 8.8e-4) and its float8 control's
# (2.9e-4, 3.9e-3, 6.4e-3 on seed 7).
TINY_LIMITS = {"loss1_rel_gap": 1e-4, "grad_leaf_gap": 2e-3,
               "change_leaf_gap": 3e-3}
# Mean gaps for 64-cell sweeps against 2,000 reference cells, between their
# sound readings (wall 5.1e-3, checkpoints 2.9e-2, failures 2.6e-2, waste
# 4.2e-2 at most over three seeds) and the faults' (wall 7.9e-2 with the
# interval doubled, checkpoints 0.50, failures 1.29, waste 0.59 and more).
ENGINE_TINY_LIMITS = {"mean_wall_gap": 0.03, "mean_checkpoints_gap": 0.15,
                      "mean_failures_gap": 0.15, "mean_waste_gap": 0.25}


class CpuChips:
    platform, kind, count = "cpu", "cpu", 1
    peaks = device.load_peaks()["TPU v5 lite"]

    def memory_peak_bytes(self):
        return 0


def tiny_trainer_cell(name="olmo-1b-2l.ckpt-every-170", **traffic):
    real = registry.resolve(name)
    cfg = dict(real.config, **TINY, limits=TINY_LIMITS)
    cfg["run"] = dict(cfg["run"], seq_len=64, global_batch=4)
    return dataclasses.replace(real, config=cfg,
                               traffic=dict(real.traffic, **traffic))


def tiny_engine_cell(**traffic):
    real = registry.resolve("engine-paper-normal.sweep-256")
    cfg = dict(real.config, reference_cells=2000,
               limits=dict(real.config["limits"], **ENGINE_TINY_LIMITS))
    return dataclasses.replace(
        real, config=cfg,
        traffic=dict(real.traffic, cells_per_sweep=64, **traffic))


def run(cell, seconds=1.0, trace=False):
    result, checks = harness.run_cell(cell, seed=SEED, seconds=seconds,
                                      trace=trace, t_process=time.monotonic(),
                                      dev=CpuChips())
    json.dumps(result)
    assert list(result)[-1] == "checks"
    return result


# --------------------------------------------------------------------------- #
# Trainer                                                                     #
# --------------------------------------------------------------------------- #

def test_trainer_sound_run_is_correct():
    r = run(tiny_trainer_cell(save_every_steps=5))
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"goodput_tokens_per_s", "save_stall_s",
                                 "setup_s"}
    assert r["checks"]["image_leaves_differing"]["value"] == 0


def _broken_step(monkeypatch, breakage):
    import repro.runtime.trainer as tr

    real = tr.make_train_step

    def factory(*a, **kw):
        step = real(*a, **kw)

        def broken(state, batch):
            return breakage(step, state, batch)
        return broken

    monkeypatch.setattr(tr, "make_train_step", factory)


def _unchanged(step, state, batch):
    _, metrics = step(state, batch)
    return state, metrics


def _half_batch(step, state, batch):
    import jax

    half = jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)
    return step(state, half)


def _one_leaf_not_updated(step, state, batch):
    new, metrics = step(state, batch)
    opt = new.opt._replace(master=dict(
        new.opt.master, embed={"tok": state.opt.master["embed"]["tok"]}))
    return new._replace(opt=opt), metrics


@pytest.mark.parametrize("breakage", [_unchanged, _half_batch,
                                      _one_leaf_not_updated],
                         ids=["state-unchanged", "half-batch",
                              "answer-altered"])
def test_trainer_fault_is_not_correct(monkeypatch, breakage):
    _broken_step(monkeypatch, breakage)
    r = run(tiny_trainer_cell("olmo-1b-half.steps"), seconds=0.5)
    assert not r["correct"], r["checks"]


def test_trainer_altered_image_is_not_correct(monkeypatch):
    import repro.ckpt.store as store

    real = store.save_pytree

    def altered(root, step, tree, n_shards=4):
        tree = dict(tree._asdict())
        tok = np.array(tree["params"]["embed"]["tok"])
        tok.reshape(-1)[7] = tok.reshape(-1)[7] + 1
        tree["params"] = dict(tree["params"], embed={"tok": tok})
        return real(root, step, tree, n_shards)

    monkeypatch.setattr(store, "save_pytree", altered)
    r = run(tiny_trainer_cell(save_every_steps=5))
    assert not r["correct"]
    assert r["checks"]["image_leaves_differing"]["value"] >= 1


def test_trainer_control_is_not_correct(monkeypatch):
    # The float8 reference in the program's place: its readings of the warm
    # steps replace the program's, and the run's own comparison judges them.
    cell = tiny_trainer_cell("olmo-1b-half.steps")
    system = registry.system("trainer")
    control = olmo.train(cell.config, cell.traffic["data"],
                         seeds.derive(SEED, system.SEED_TAG),
                         int(cell.traffic["warm_steps"]), precision="fp8")
    real_call = system.StepProbe.__call__

    def call(self, state, batch):
        out = real_call(self, state, batch)
        if self.calls == self.warm:
            self.warm_losses = list(control.losses)
            self.grad_norms = dict(control.grad_norms)
            self.change_norms = dict(control.change_norms)
        return out

    monkeypatch.setattr(system.StepProbe, "__call__", call)
    monkeypatch.setattr(registry, "system", lambda name, root=None: system)
    r = run(cell, seconds=0.5)
    assert not r["correct"], r["checks"]


# --------------------------------------------------------------------------- #
# Engine                                                                      #
# --------------------------------------------------------------------------- #

def test_engine_sound_run_is_correct():
    r = run(tiny_engine_cell())
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"engine_cells_per_s", "setup_s"}
    assert {"mean_wall_gap", "mean_checkpoints_gap", "mean_failures_gap",
            "mean_waste_gap"} <= set(r["checks"])


@pytest.mark.parametrize("name", sorted(faults.ENGINE))
def test_engine_control_and_faults_are_not_correct(name):
    cell = tiny_engine_cell()
    with faults.planted(faults.ENGINE[name](cell.config)):
        r = run(cell, seconds=0.5)
    assert not r["correct"], r["checks"]
