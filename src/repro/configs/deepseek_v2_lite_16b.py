"""deepseek-v2-lite-16b — MLA (kv_lora=512, YaRN rope) + fine-grained MoE.

[arXiv:2405.04434; hf] 27L d_model=2048 16H vocab=102400; layer 0 dense
SwiGLU (d_ff=10944), layers 1-26 MoE: 64 routed experts of width 1408
(moe_d_ff), softmax gate, greedy top-6 without renormalisation, + 2 shared
experts.
Cache stores the compressed latent (kv_lora_rank + qk_rope_dim = 576/token).
Rope: YaRN, factor 40 over 4096 original positions, mscale 0.707.
"""
from repro.configs.base import ModelConfig, RopeScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=10944,
    vocab_size=102400,
    attn_kind="mla",
    rope_theta=10000.0,
    rope_scaling=RopeScaling(factor=40.0,
                             original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0,
                             mscale=0.707, mscale_all_dim=0.707),
    kv_lora_rank=512,
    qk_rope_dim=64,
    qk_nope_dim=128,
    v_head_dim=128,
    n_experts=64,
    n_shared_experts=2,
    top_k=6,
    moe_d_ff=1408,
    first_dense_layers=1,
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    source="arXiv:2405.04434; hf",
)
