"""Model registry: config -> Model, plus dry-run input specs per shape cell."""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig


class Model(NamedTuple):
    config: ModelConfig
    init: Callable
    param_axes: Callable
    loss: Callable          # (params, batch) -> scalar
    prefill: Callable       # (params, batch) -> (logits [B,V], cache)
    decode_step: Callable   # (params, cache, tokens [B]) -> (logits, cache)
    extend: Callable        # (params, cache, tokens [B,Sn], lens_new) -> ...
    init_cache: Callable    # (b, max_len) -> cache pytree
    family: str
    extras: dict


def build_model(cfg: ModelConfig) -> Model:
    if cfg.is_encdec:
        from repro.models.encdec import build_encdec
        fns = build_encdec(cfg)
    else:
        from repro.models.lm import build_lm
        fns = build_lm(cfg)
    extras = {k: v for k, v in fns.items()
              if k not in Model._fields and k != "family"}
    return Model(
        config=cfg,
        init=fns["init"],
        param_axes=fns["param_axes"],
        loss=fns["loss"],
        prefill=fns["prefill"],
        decode_step=fns["decode_step"],
        extend=fns["extend"],
        init_cache=fns["init_cache"],
        family=fns["family"],
        extras=extras,
    )


def _tok_spec(shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of a dry-run cell.

    Conventions (DESIGN.md §4):
      * vlm: seq_len = n_patches + text tokens; patch embeddings are a
        stub input [B, P, D] float.
      * audio (enc-dec): seq_len refers to the decoder; the encoder consumes
        src_len=1024 frame embeddings [B, src, D] float.
      * decode shapes: the cache covers seq_len tokens of context; inputs are
        the cache pytree + one token per sequence (handled by the launcher
        via ``decode_state_specs``).
    """
    b = shape.global_batch
    s = shape.seq_len
    batch: dict[str, Any] = {}
    if cfg.family == "vlm":
        n_text = max(s - cfg.n_patches, 1)
        batch["tokens"] = _tok_spec((b, n_text))
        batch["patches"] = jax.ShapeDtypeStruct((b, cfg.n_patches, cfg.d_model),
                                                jnp.dtype(cfg.dtype))
    elif cfg.is_encdec:
        batch["tokens"] = _tok_spec((b, s))
        batch["frames"] = jax.ShapeDtypeStruct((b, cfg.src_len, cfg.d_model),
                                               jnp.dtype(cfg.dtype))
    else:
        batch["tokens"] = _tok_spec((b, s))
    return batch


def decode_state_specs(model: Model, shape: ShapeConfig):
    """(cache_specs, token_specs) for lowering decode_step without allocation."""
    b, s = shape.global_batch, shape.seq_len
    cache = jax.eval_shape(lambda: model.init_cache(b, s))
    tokens = _tok_spec((b,))
    return cache, tokens


def cache_axes(model: Model):
    """Logical-axis strings mirroring init_cache's pytree (for shardings)."""
    cfg = model.config
    if cfg.is_encdec:
        return {
            "k": "layers batch cache_seq kv_heads head_dim",
            "v": "layers batch cache_seq kv_heads head_dim",
            "xk": "layers batch src_seq kv_heads head_dim",
            "xv": "layers batch src_seq kv_heads head_dim",
            "slot_pos": "batch cache_seq", "pos": "batch",
        }
    if model.family == "rwkv":
        return {"pos": "batch",
                "states": ("layers batch embed",
                           "layers batch heads head_dim state",
                           "layers batch embed")}
    if model.family == "zamba":
        from repro.models.lm import _zamba_groups
        g, per, tail = _zamba_groups(cfg)
        mamba = {"groups": ("groups layers batch conv_k inner",
                            "groups layers batch heads head_dim state")}
        if tail:
            mamba["tail"] = ("layers batch conv_k inner",
                             "layers batch heads head_dim state")
        return {
            "pos": "batch", "slot_pos": "batch cache_seq", "mamba": mamba,
            "attn_k": "groups batch cache_seq kv_heads head_dim",
            "attn_v": "groups batch cache_seq kv_heads head_dim",
        }
    # attention stacks
    from repro.models.lm import _make_stacks
    ax: dict = {"pos": "batch", "slot_pos": "batch cache_seq"}
    for i, _spec in enumerate(_make_stacks(cfg)):
        if cfg.attn_kind == "mla":
            ax[f"stack{i}"] = {"ckv": "layers batch cache_seq kv_lora",
                               "krope": "layers batch cache_seq qk_dim"}
        else:
            ax[f"stack{i}"] = {
                "k": "layers batch cache_seq kv_heads head_dim",
                "v": "layers batch cache_seq kv_heads head_dim"}
    if cfg.is_moe:
        ax["moe_stats"] = "."
    return ax
