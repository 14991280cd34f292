"""The plain OLMo reference draws the same weights and batches as the
program from a seed, and at float32 computes the program's loss."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import ckpt_process, olmo

SIZES = {"hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "intermediate_size": 256,
         "vocab_size": 256, "num_hidden_layers": 2, "rope_theta": 10000.0,
         "layer_norm_eps": 1e-5}


@pytest.fixture(scope="module")
def program():
    from repro.configs.olmo_1b import SMOKE

    return dataclasses.replace(SMOKE, compute_dtype="float32")


def _leaves_by_name(tree):
    return {str(getattr(p[-1], "key", p[-1])): x
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def test_same_weights_and_batches(program):
    from repro.data import DataConfig, SyntheticLM
    from repro.models.model import init_params

    seed = 2 ** 31 - 7
    got = _leaves_by_name(init_params(jax.random.key(seed), program))
    want = olmo.init(seed, olmo.Sizes.of(SIZES))
    assert set(want) == set(got)
    for k in want:
        assert want[k].dtype == jnp.bfloat16
        assert bool(jnp.all(want[k] == got[k])), k
    b = SyntheticLM(DataConfig(vocab=256, seq_len=64, global_batch=4,
                               seed=seed)).batch_at(5)
    rb = olmo.batch(seed, 5, 4, 64, 256, 1.3, 0.3)
    assert np.array_equal(b["tokens"], rb["tokens"])
    assert np.array_equal(b["labels"], rb["labels"])


def test_float32_loss_matches_the_program(program):
    from repro.models.model import init_params, loss_fn

    seed = 99
    s = olmo.Sizes.of(SIZES)
    bt = olmo.batch(seed, 0, 4, 64, 256, 1.3, 0.3)
    with jax.default_matmul_precision("highest"):
        want, _ = loss_fn(init_params(jax.random.key(seed), program), bt,
                          program)
        master = jax.tree.map(lambda w: w.astype(jnp.float32),
                              olmo.init(seed, s))
        got = sum(float(olmo.row_nll_sum(master, bt["tokens"][r],
                                         bt["labels"][r], s, False))
                  for r in range(4)) / (4 * 64)
    # Both float32 at highest precision: rounding of sums only.
    assert got == pytest.approx(float(want), rel=1e-5)


def test_eq11_interval_and_lambert_w():
    w = ckpt_process.lambert_w0(-0.2)
    assert w * np.exp(w) == pytest.approx(-0.2, rel=1e-12)
    rate, v = 1 / 250, 20.0
    t = ckpt_process.interval(rate, v)
    cost = lambda x: (np.exp(rate * (x + v)) - 1) / (rate * x)
    assert cost(t) < cost(0.95 * t) and cost(t) < cost(1.05 * t)
