"""Transformer/SSM block assembly for every assigned architecture family."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import FFN_AXES, ffn_apply, ffn_init, normal_init, rms_norm


def _res(x):
    """Residual-stream constraint: partial sums from TP-contracted matmuls
    become reduce-scatters over the sequence (Megatron-SP) instead of full
    fp32 all-reduces — the dominant §Perf win on the train cells."""
    if x.ndim == 3:
        return shard(x, "batch", "seq", "embed")
    return shard(x, "batch", "embed")


# ---------------- dense / moe attention blocks ----------------
#: stable names of the two halves of an attention block in the compiled
#: programs (op metadata), so a device trace can attribute the engine's
#: operations to attention or MLP
ATTN_SCOPE, MLP_SCOPE = "iemas.engine/attention", "iemas.engine/mlp"


def attn_block_init(key, cfg, dtype, *, ffn_kind: str, d_ff: int | None = None):
    """ffn_kind: dense | moe."""
    k1, k2 = jax.random.split(key)
    if cfg.attn_kind == "mla":
        a = attn.mla_init(k1, cfg, dtype)
    else:
        a = attn.gqa_init(k1, cfg, dtype)
    p = {"ln1": jnp.ones((cfg.d_model,), dtype), "attn": a,
         "ln2": jnp.ones((cfg.d_model,), dtype)}
    if ffn_kind == "dense":
        p["mlp"] = ffn_init(k2, cfg.d_model, d_ff or cfg.d_ff, dtype)
    else:
        p["moe"] = moe_mod.moe_init(k2, cfg, dtype)
    return p


def attn_block_axes(cfg, *, ffn_kind: str):
    a = attn.mla_axes(cfg) if cfg.attn_kind == "mla" else attn.gqa_axes(cfg)
    ax = {"ln1": "embed", "attn": a, "ln2": "embed"}
    if ffn_kind == "dense":
        ax["mlp"] = dict(FFN_AXES)
    else:
        ax["moe"] = moe_mod.moe_axes(cfg)
    return ax


def block_ffn(p, x, cfg, ffn_kind: str, valid=None, layer=None):
    """The block's second half: RMSNorm, then the dense SwiGLU or the MoE,
    residual. ``valid`` marks the positions that route (MoE; None: all);
    ``layer``: see `moe.moe_ffn`. Returns (x, stats): the MoE's int32[2]
    (rows, experts) counts, or None for a dense block."""
    with jax.named_scope(MLP_SCOPE):
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if ffn_kind == "dense":
            return x + ffn_apply(p["mlp"], h), None
        out, stats = moe_mod.moe_ffn(p["moe"], h, cfg, valid, layer)
        return x + out, stats


def attn_block_parallel(p, x, cfg, *, ffn_kind: str, lens=None, layer=None):
    """Returns (x, kv, stats): kv are the cacheables of this layer, stats
    `block_ffn`'s."""
    with jax.named_scope(ATTN_SCOPE):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        if cfg.attn_kind == "mla":
            o, kv = attn.mla_parallel(p["attn"], h, cfg, lens=lens)
        else:
            o, kv = attn.gqa_parallel(p["attn"], h, cfg, lens=lens)
        x = _res(x + _res(o))
    valid = None if lens is None else (
        jnp.arange(x.shape[1])[None, :] < lens[:, None])
    x, stats = block_ffn(p, x, cfg, ffn_kind, valid, layer)
    return _res(x), kv, stats


def attn_block_decode(p, x, cache_layer, cfg, *, ffn_kind: str, layer=None):
    """One token per row. Returns (x, new cache layer, stats)."""
    with jax.named_scope(ATTN_SCOPE):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        if cfg.attn_kind == "mla":
            o, new_cache = attn.mla_decode(p["attn"], h, cache_layer, cfg)
        else:
            o, new_cache = attn.gqa_decode(p["attn"], h, cache_layer, cfg)
        x = x + o
    x, stats = block_ffn(p, x, cfg, ffn_kind, layer=layer)
    return x, new_cache, stats


# ---------------- RWKV6 block ----------------

def rwkv_block_init(key, cfg, dtype):
    k1, _ = jax.random.split(key)
    return {"ln1": jnp.ones((cfg.d_model,), dtype),
            "ln2": jnp.ones((cfg.d_model,), dtype),
            "mix": ssm_mod.rwkv6_init(k1, cfg, dtype)}


def rwkv_block_axes(cfg):
    return {"ln1": "embed", "ln2": "embed", "mix": ssm_mod.rwkv6_axes(cfg)}


def rwkv_block_parallel(p, x, cfg, state=None):
    """state: (shift_t [B,D], wkv [B,H,hd,hd], shift_c [B,D]) or None."""
    shift_t, wkv, shift_c = state if state is not None else (None, None, None)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    o, (new_shift_t, new_wkv) = ssm_mod.rwkv6_time_mix(
        p["mix"], h, cfg, shift_state=shift_t, wkv_state=wkv, parallel=True)
    x = _res(x + _res(o))
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    o, new_shift_c = ssm_mod.rwkv6_channel_mix(p["mix"], h, shift_state=shift_c,
                                               parallel=True)
    x = _res(x + o)
    return x, (new_shift_t, new_wkv, new_shift_c)


def rwkv_block_step(p, x, cfg, state):
    shift_t, wkv, shift_c = state
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    o, (new_shift_t, new_wkv) = ssm_mod.rwkv6_time_mix(
        p["mix"], h, cfg, shift_state=shift_t, wkv_state=wkv, parallel=False)
    x = x + o
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    o, new_shift_c = ssm_mod.rwkv6_channel_mix(p["mix"], h, shift_state=shift_c,
                                               parallel=False)
    x = x + o
    return x, (new_shift_t, new_wkv, new_shift_c)


# ---------------- Mamba2 block (zamba2 backbone) ----------------

def mamba_block_init(key, cfg, dtype):
    return {"ln": jnp.ones((cfg.d_model,), dtype),
            "mix": ssm_mod.mamba2_init(key, cfg, dtype)}


def mamba_block_axes(cfg):
    return {"ln": "embed", "mix": ssm_mod.mamba2_axes(cfg)}


def mamba_block_parallel(p, x, cfg, state=None):
    conv, ssm = state if state is not None else (None, None)
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    o, (new_conv, new_ssm) = ssm_mod.mamba2_block(
        p["mix"], h, cfg, conv_state=conv, ssm_state=ssm, parallel=True)
    return _res(x + _res(o)), (new_conv, new_ssm)


def mamba_block_step(p, x, cfg, state):
    conv, ssm = state
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    o, (new_conv, new_ssm) = ssm_mod.mamba2_block(
        p["mix"], h, cfg, conv_state=conv, ssm_state=ssm, parallel=False)
    return x + o, (new_conv, new_ssm)


# ---------------- zamba2 shared attention block (+ per-invocation LoRA) ----

LORA_SHARED = 64


def shared_attn_init(key, cfg, dtype, n_groups: int):
    """One shared GQA+MLP block, with stacked per-invocation q/k/v LoRAs."""
    k1, k2, k3 = jax.random.split(key, 3)
    base = attn_block_init(k1, cfg, dtype, ffn_kind="dense")
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(k3, 6)
    lora = {
        "qa": normal_init(ks[0], (n_groups, d, LORA_SHARED), d, dtype),
        "qb": jnp.zeros((n_groups, LORA_SHARED, h, hd), dtype),
        "ka": normal_init(ks[1], (n_groups, d, LORA_SHARED), d, dtype),
        "kb": jnp.zeros((n_groups, LORA_SHARED, kv, hd), dtype),
        "va": normal_init(ks[2], (n_groups, d, LORA_SHARED), d, dtype),
        "vb": jnp.zeros((n_groups, LORA_SHARED, kv, hd), dtype),
    }
    return {"block": base, "lora": lora}


def shared_attn_axes(cfg):
    return {
        "block": attn_block_axes(cfg, ffn_kind="dense"),
        "lora": {
            "qa": "groups embed lora_rank", "qb": "groups lora_rank heads head_dim",
            "ka": "groups embed lora_rank", "kb": "groups lora_rank kv_heads head_dim",
            "va": "groups embed lora_rank", "vb": "groups lora_rank kv_heads head_dim",
        },
    }


def _lora_qkv_delta(lora_g, h):
    """Per-invocation low-rank q/k/v deltas. h: [..., D]."""
    dq = jnp.einsum("...r,rhk->...hk", jnp.einsum("...d,dr->...r", h, lora_g["qa"]), lora_g["qb"])
    dk = jnp.einsum("...r,rhk->...hk", jnp.einsum("...d,dr->...r", h, lora_g["ka"]), lora_g["kb"])
    dv = jnp.einsum("...r,rhk->...hk", jnp.einsum("...d,dr->...r", h, lora_g["va"]), lora_g["vb"])
    return dq, dk, dv


def shared_attn_parallel(p, lora_g, x, cfg, *, lens=None):
    from repro.models.layers import apply_rope

    blk = p["block"]
    h = rms_norm(x, blk["ln1"], cfg.norm_eps)
    q, k, v = attn._qkv(blk["attn"], h, cfg)
    dq, dk, dv = _lora_qkv_delta(lora_g, h)
    q, k, v = q + dq, k + dk, v + dv
    pos = jnp.arange(x.shape[1])
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    o = attn.attend_parallel(q, k, v, causal=True, kv_valid_len=lens)
    x = _res(x + _res(jnp.einsum("...hk,hkd->...d", o, blk["attn"]["wo"])))
    h = rms_norm(x, blk["ln2"], cfg.norm_eps)
    x = _res(x + ffn_apply(blk["mlp"], h))
    return x, (k, v)


def shared_attn_decode(p, lora_g, x, cache_layer, cfg):
    from repro.models.layers import apply_rope

    blk = p["block"]
    pos = cache_layer["pos"]
    h = rms_norm(x, blk["ln1"], cfg.norm_eps)
    q, k, v = attn._qkv(blk["attn"], h[:, None, :], cfg)
    dq, dk, dv = _lora_qkv_delta(lora_g, h[:, None, :])
    q, k, v = q + dq, k + dk, v + dv
    q = apply_rope(q, pos[:, None], cfg.rope_theta)[:, 0]
    k = apply_rope(k, pos[:, None], cfg.rope_theta)[:, 0]
    v = v[:, 0]
    kc, vc, sp = attn.cache_append(cache_layer["k"], cache_layer["v"],
                                   cache_layer["slot_pos"], k, v, pos)
    o = attn.attend_decode(q, kc, vc, sp, pos)
    x = x + jnp.einsum("bhk,hkd->bd", o, blk["attn"]["wo"])
    h = rms_norm(x, blk["ln2"], cfg.norm_eps)
    x = x + ffn_apply(blk["mlp"], h)
    return x, {"k": kc, "v": vc, "slot_pos": sp, "pos": pos + 1}
