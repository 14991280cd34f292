"""OLMo blocks for the trainer system: the program's ModelConfig for a
configuration file, and the operations a training step needs.

Model FLOPs per token for a dense decoder (PaLM, arXiv:2204.02311, App. B):
6 per matmul parameter (forward 2, backward 4) plus, per layer, 12 x seq
x heads x head_dim for the attention scores and their weighted sum over
the whole sequence.  Recomputation (remat) is not counted; the embedding
lookup is not a matmul, the tied unembedding is.
"""
from __future__ import annotations

from typing import Any, Dict


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


def matmul_params(cfg: Dict[str, Any]) -> int:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    kv = int(cfg["num_key_value_heads"])
    hd = d // h
    ff = int(cfg["intermediate_size"])
    per_layer = d * hd * (2 * h + 2 * kv) + 3 * d * ff   # q, o, k, v; SwiGLU
    return int(cfg["num_hidden_layers"]) * per_layer + int(cfg["vocab_size"]) * d


def train_flops_per_token(cfg: Dict[str, Any]) -> float:
    seq = int(cfg["run"]["seq_len"])
    attn = 12 * int(cfg["num_hidden_layers"]) * seq * int(cfg["hidden_size"])
    return 6.0 * matmul_params(cfg) + attn
