"""The program's ``ModelConfig`` for a dense decoder's configuration file.

Starts from the program's own config of ``cfg["arch"]`` and sets every key the
file states, so the program runs as the file says.
"""
from __future__ import annotations

import dataclasses

NORMS = {"rmsnorm": "rmsnorm", "layernorm_no_affine": "nonparam_ln"}


def program_config(cfg: dict, base):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dataclasses.replace(
        base,
        num_layers=cfg["num_hidden_layers"], d_model=d, num_heads=h,
        num_kv_heads=cfg["num_key_value_heads"], head_dim=d // h,
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        qkv_bias=bool(cfg["qkv_bias"]), norm_kind=NORMS[cfg["norm"]],
        mlp_kind="swiglu", param_dtype=cfg["param_dtype"],
        compute_dtype=cfg["compute_dtype"],
        optimizer_dtype=cfg["optimizer_dtype"])
