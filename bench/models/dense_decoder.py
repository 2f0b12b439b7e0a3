"""The program's side of a Llama-style dense decoder configuration.

Maps a configuration file (published key names) onto the program's
``ModelConfig``. The plain reference of the same model is
``bench/reference/dense_decoder.py``.
"""

from __future__ import annotations


def program_config(cfg: dict):
    from repro.configs import ModelConfig

    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return ModelConfig(
        name=cfg["name"],
        family="dense",
        num_layers=int(cfg["num_hidden_layers"]),
        d_model=d,
        num_heads=heads,
        num_kv_heads=int(cfg["num_key_value_heads"]),
        d_ff=int(cfg["intermediate_size"]),
        vocab_size=int(cfg["vocab_size"]),
        head_dim=int(cfg.get("head_dim") or d // heads),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=cfg["torch_dtype"],
        compute_dtype=cfg["torch_dtype"],
        source=cfg["source"],
    )
