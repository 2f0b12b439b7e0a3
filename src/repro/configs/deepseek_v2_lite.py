"""DeepSeek-V2-Lite 16B-A2.4B [arXiv:2405.04434; hf config.json].

Multi-head latent attention without q compression (16 heads; kv latent
512 wide, q/k 128 + 64 rotary, v 128) under YaRN rotary scaling (factor
40 over 4,096 positions), and DeepSeekMoE: the first layer dense (d_ff
10944), then 64 routed experts of width 1408 with softmax scores, greedy
top-6 without renormalisation, 2 shared experts and a sequence-wise
balance loss (alpha 0.001, not in config.json). 15,706,484,224 parameters.
"""

from .base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1408,
    vocab_size=102400,
    moe_num_experts=64,
    moe_top_k=6,
    moe_num_shared=2,
    moe_first_dense=1,
    moe_dense_ff=10944,
    moe_norm_topk=False,
    moe_aux="seq",
    router_aux_weight=0.001,
    attn_kind="mla",
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    norm_eps=1e-6,
    source="arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite config.json",
)
