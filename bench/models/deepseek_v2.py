"""The program's side of a DeepSeek-V2 configuration (MLA + DeepSeekMoE).

Maps a configuration file (the published ``config.json`` keys, with the
one-chip cut stated beside them) onto the program's ``ModelConfig``. The
file's ``n_routed_experts`` is the count held on this chip; the router's
width is the published count, and ``expert_parallel.first_expert`` is the
global id of the first held expert. The plain reference of the same model
is ``bench/reference/deepseek_v2.py``.
"""

from __future__ import annotations


def check_supported(cfg: dict) -> None:
    """The parts of the DeepSeek-V2 family the program and the reference
    compute: no q compression, softmax scores, greedy top-k over one
    group with the gate weights unscaled, an expert layer after every
    leading dense layer, YaRN."""
    want = {"q_lora_rank": None, "scoring_func": "softmax", "topk_method": "greedy",
            "n_group": 1, "topk_group": 1, "moe_layer_freq": 1, "hidden_act": "silu",
            "attention_bias": False, "routed_scaling_factor": 1}
    for key, value in want.items():
        if cfg[key] != value:
            raise ValueError(f"{key}={cfg[key]!r}: only {value!r} is built")
    if cfg["rope_scaling"]["type"] != "yarn":
        raise ValueError(f"rope_scaling {cfg['rope_scaling']['type']!r}: only yarn is built")


def program_config(cfg: dict):
    from repro.configs import ModelConfig

    check_supported(cfg)
    rope = cfg["rope_scaling"]
    return ModelConfig(
        name=cfg["name"],
        family="moe",
        num_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]),
        num_heads=int(cfg["num_attention_heads"]),
        num_kv_heads=int(cfg["num_attention_heads"]),
        d_ff=int(cfg["moe_intermediate_size"]),
        vocab_size=int(cfg["vocab_size"]),
        moe_num_experts=int(cfg["published"]["n_routed_experts"]),
        moe_experts_held=int(cfg["n_routed_experts"]),
        moe_expert_offset=int(cfg["expert_parallel"]["first_expert"]),
        moe_top_k=int(cfg["num_experts_per_tok"]),
        moe_num_shared=int(cfg["n_shared_experts"]),
        moe_first_dense=int(cfg["first_k_dense_replace"]),
        moe_dense_ff=int(cfg["intermediate_size"]),
        moe_norm_topk=bool(cfg["norm_topk_prob"]),
        moe_aux="seq" if cfg["seq_aux"] else "switch",
        router_aux_weight=float(cfg["aux_loss_alpha"]),
        attn_kind="mla",
        kv_lora_rank=int(cfg["kv_lora_rank"]),
        qk_nope_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_dim=int(cfg["qk_rope_head_dim"]),
        v_head_dim=int(cfg["v_head_dim"]),
        yarn_factor=float(rope["factor"]),
        yarn_original_max_pos=int(rope["original_max_position_embeddings"]),
        yarn_beta_fast=float(rope["beta_fast"]),
        yarn_beta_slow=float(rope["beta_slow"]),
        yarn_mscale=float(rope["mscale"]),
        yarn_mscale_all_dim=float(rope["mscale_all_dim"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=cfg["torch_dtype"],
        compute_dtype=cfg["torch_dtype"],
        source=cfg["source"],
    )
