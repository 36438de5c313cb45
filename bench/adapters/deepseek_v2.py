"""The program's side of the DeepSeek-V2 family (MLA with YaRN rope, dense
leading layers, then MoE layers of routed and shared experts).

A configuration's ``engine.family`` names this file and
`reference/<family>.py`. This one holds what the harness needs from the
program for the family, so the reference beside it imports nothing of the
program:

* ``program_config``: the program's ``ModelConfig`` for the configuration
  file's own (Hugging Face) keys; the serving engines are built from it.
  Keys whose values the program does not implement are refused.
"""
from __future__ import annotations

import dataclasses


def _refuse(config: dict) -> None:
    """Raise on published settings that the program does not implement."""
    why = []
    if config.get("q_lora_rank") is not None:
        why.append("a q LoRA (q_lora_rank is not null)")
    if int(config.get("n_group") or 1) > 1:
        why.append("grouped routing (n_group > 1)")
    if config.get("topk_method", "greedy") != "greedy":
        why.append(f"top-k method {config['topk_method']!r}")
    if config.get("scoring_func", "softmax") != "softmax":
        why.append(f"scoring function {config['scoring_func']!r}")
    if int(config.get("moe_layer_freq", 1)) != 1:
        why.append("MoE layers other than every layer after the dense ones")
    if config.get("attention_bias") or config.get("tie_word_embeddings"):
        why.append("attention bias or a tied LM head")
    if config.get("hidden_act", "silu") != "silu":
        why.append(f"activation {config['hidden_act']!r}")
    rs = config.get("rope_scaling")
    if rs and rs.get("type", rs.get("rope_type")) != "yarn":
        why.append(f"rope scaling {rs!r}")
    if why:
        raise ValueError("the program does not implement " + "; ".join(why))


def program_config(config: dict):
    """The program's `ModelConfig` for a DeepSeek-V2 configuration file's
    keys."""
    from repro.configs import get_config
    from repro.configs.base import RopeScaling

    _refuse(config)
    rs = config.get("rope_scaling")
    scaling = None if not rs else RopeScaling(
        factor=float(rs["factor"]),
        original_max_position_embeddings=int(
            rs["original_max_position_embeddings"]),
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]),
        mscale_all_dim=float(rs.get("mscale_all_dim", 0)))
    heads = int(config["num_attention_heads"])
    return dataclasses.replace(
        get_config("deepseek-v2-lite-16b"),
        n_layers=int(config["num_hidden_layers"]),
        d_model=int(config["hidden_size"]),
        n_heads=heads, n_kv_heads=heads,
        head_dim=int(config["qk_nope_head_dim"]),
        d_ff=int(config["intermediate_size"]),
        vocab_size=int(config["vocab_size"]),
        rope_theta=float(config["rope_theta"]),
        rope_scaling=scaling,
        kv_lora_rank=int(config["kv_lora_rank"]),
        qk_rope_dim=int(config["qk_rope_head_dim"]),
        qk_nope_dim=int(config["qk_nope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        n_experts=int(config["n_routed_experts"]),
        n_shared_experts=int(config["n_shared_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        moe_d_ff=int(config["moe_intermediate_size"]),
        first_dense_layers=int(config["first_k_dense_replace"]),
        norm_topk_prob=bool(config["norm_topk_prob"]),
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_eps=float(config["rms_norm_eps"]),
        qkv_bias=False, tie_embeddings=False,
        dtype=str(config["torch_dtype"]))
