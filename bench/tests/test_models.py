"""The trainer finds its model by the configuration's ``model_type``: the
OLMo cells build the same ModelConfig, count the same FLOPs and read the
same ``train_step_mfu`` as when the trainer built OLMo by name, and a new
model type is new files only."""
import json
from types import SimpleNamespace

import pytest

from bench import registry

TRAINER_CELLS = [w["name"] for w in registry.load_benchmark()["workloads"]
                 if registry.resolve(w["name"]).config["system"] == "trainer"]


def _config(name):
    return json.loads((registry.BENCH_DIR / f"configs/{name}.json")
                      .read_text())


@pytest.mark.parametrize("name,layers", [("olmo-1b-2l", 2),
                                         ("olmo-1b-half", 8)])
def test_olmo_model_config_is_unchanged(name, layers):
    from repro.configs.base import AttentionConfig, ModelConfig, RopeConfig

    cfg = _config(name)
    assert cfg["model_type"] == "olmo"
    # What the trainer built before the lookup, field by field.
    want = ModelConfig(
        name=name, family="dense", n_layers=layers, d_model=2048, d_ff=8192,
        vocab=50304,
        attention=AttentionConfig(n_heads=16, n_kv_heads=16, head_dim=128,
                                  rope=RopeConfig(theta=10000.0)),
        norm="nonparametric", norm_eps=1e-05, act="silu_gated",
        tie_embeddings=True, param_dtype="bfloat16",
        compute_dtype="bfloat16", remat="full")
    assert registry.model("olmo").model_config(cfg) == want


@pytest.mark.parametrize("name,flops", [("olmo-1b-2l", 1_524_105_216.0),
                                        ("olmo-1b-half", 4_242_014_208.0)])
def test_olmo_flops_per_token_are_unchanged(name, flops):
    assert registry.model("olmo").train_flops_per_token(_config(name)) \
        == flops


def test_train_step_mfu_reading_is_unchanged():
    # 113 steps of 4 x 2048 tokens in 50.123 s on one chip: 39.768... %
    # with the FLOP count read as the metric read it before the lookup.
    ctx = {"tokens": 8192 * 113, "window_s": 50.123,
           "trace": SimpleNamespace(n_devices=1),
           "peaks": {"bf16_flops_per_s": 197e12},
           "config": _config("olmo-1b-half")}
    assert registry.metric_reader("train_step_mfu").read(ctx) \
        == 39.76831800156152


def test_every_trainer_cell_names_a_model_with_its_files():
    assert TRAINER_CELLS
    for name in TRAINER_CELLS:
        model_type = registry.resolve(name).config["model_type"]
        assert registry.model(model_type).model_config
        assert registry.model(model_type).train_flops_per_token
        assert registry.reference(model_type).train


def test_a_new_model_type_needs_only_new_files(tmp_path):
    (tmp_path / "bench/models").mkdir(parents=True)
    (tmp_path / "bench/reference").mkdir(parents=True)
    (tmp_path / "bench/models/toy.py").write_text(
        "def model_config(cfg):\n    return ('toy', cfg['width'])\n\n\n"
        "def train_flops_per_token(cfg):\n    return 6.0 * cfg['width']\n")
    (tmp_path / "bench/reference/toy.py").write_text(
        "def train(cfg, data, seed, n_steps, precision='highest', rows=None):"
        "\n    return [seed] * n_steps\n")
    cfg = {"model_type": "toy", "width": 3}
    model = registry.model(cfg["model_type"], root=tmp_path)
    assert model.model_config(cfg) == ("toy", 3)
    assert model.train_flops_per_token(cfg) == 18.0
    assert registry.reference("toy", root=tmp_path).train(
        cfg, {}, 7, 2) == [7, 7]
    with pytest.raises(KeyError):
        registry.model("no_such_model", root=tmp_path)
    with pytest.raises(KeyError):
        registry.reference("no_such_model", root=tmp_path)
