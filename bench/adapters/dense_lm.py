"""The program's side of the dense GQA + qk-norm family (Qwen3).

A configuration's ``engine.family`` names this file and
`reference/<family>.py`. This one holds what the harness needs from the
program for the family, so the reference beside it imports nothing of the
program:

* ``program_config``: the program's ``ModelConfig`` for the configuration
  file's own (Hugging Face) keys; the serving engines are built from it.
"""
from __future__ import annotations

import dataclasses


def program_config(config: dict):
    """The program's `ModelConfig` for a dense GQA + qk-norm configuration
    file's keys."""
    from repro.configs import get_config

    if config.get("attention_bias") or config.get("tie_word_embeddings"):
        raise ValueError("the dense reference has no attention bias and an "
                         "untied LM head")
    return dataclasses.replace(
        get_config("qwen3-8b"),
        n_layers=int(config["num_hidden_layers"]),
        d_model=int(config["hidden_size"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        d_ff=int(config["intermediate_size"]),
        vocab_size=int(config["vocab_size"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        qk_norm=True, qkv_bias=False, tie_embeddings=False,
        dtype=str(config["torch_dtype"]))
