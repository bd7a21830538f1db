"""The program's model for a dense GQA configuration file.

The builder side of the ``dense_gqa`` family; its plain reference is
``bench/reference/dense_gqa.py``.  A configuration the program cannot run
as stated is refused here, never run otherwise.
"""
from __future__ import annotations


def build_model(cfg: dict):
    from repro.models.transformer import LMConfig, TransformerLM
    if cfg["hidden_act"] != "silu" or cfg.get("attention_bias") \
            or cfg.get("mlp_bias"):
        raise ValueError("only SwiGLU FFNs without projection biases run")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("the program holds a separate unembed matrix")
    if float(cfg["rms_norm_eps"]) != 1e-6:
        raise ValueError("the program's RMSNorm epsilon is 1e-6")
    return TransformerLM(LMConfig(
        name=cfg["name"], n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], head_dim=cfg["head_dim"],
        qk_norm=bool(cfg.get("qk_norm", False)),
        rope_theta=float(cfg["rope_theta"])))
