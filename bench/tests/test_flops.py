"""The model FLOP count against a count by hand."""
import json

from bench import flops, registry


def test_olmo_1b_half_by_hand():
    cfg = json.loads((registry.BENCH_DIR / "configs/olmo-1b-half.json")
                     .read_text())
    # Per layer: q, k, v, o are 4 x 2048 x 2048 = 16,777,216; SwiGLU's
    # up, gate, down are 3 x 2048 x 8192 = 50,331,648.  Eight layers:
    # 536,870,912.  Tied head: 50,304 x 2048 = 103,022,592.
    assert flops.matmul_params(cfg) == 639_893_504
    # 6 x 639,893,504 + 12 x 8 layers x 2048 seq x 2048 width.
    assert flops.train_flops_per_token(cfg) == 3_839_361_024 + 402_653_184


def test_two_layer_cut_by_hand():
    cfg = json.loads((registry.BENCH_DIR / "configs/olmo-1b-2l.json")
                     .read_text())
    assert flops.matmul_params(cfg) == 2 * 67_108_864 + 103_022_592
