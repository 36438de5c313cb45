"""Shared model primitives: norms, RoPE, SwiGLU, inits, losses.

No flax/haiku available — parameters are plain nested dicts of jnp arrays,
and every module is a pair of functions (init, apply). Logical sharding axes
for each parameter live in a mirror pytree of space-separated axis strings
(see repro.distributed.sharding.param_shardings).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def normal_init(key, shape, fan_in: int, dtype, scale: float = 1.0):
    std = scale / max(fan_in, 1) ** 0.5
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def rms_norm(x, weight, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return ((xf * rms) * weight.astype(jnp.float32)).astype(x.dtype)


def group_norm_heads(x, weight, bias, n_heads: int, eps: float = 1e-5):
    """Per-head group norm over [..., n_heads*head_dim] (RWKV6 output norm)."""
    orig = x.shape
    xf = x.astype(jnp.float32).reshape(*orig[:-1], n_heads, orig[-1] // n_heads)
    mean = xf.mean(-1, keepdims=True)
    var = xf.var(-1, keepdims=True)
    xf = (xf - mean) * jax.lax.rsqrt(var + eps)
    xf = xf.reshape(orig)
    return (xf * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


# ---------------- RoPE ----------------

def rope_freqs(head_dim: int, theta: float):
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term ``0.1 * mscale * ln(factor) + 1``
    (1 where the context is not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(head_dim: int, theta: float, scaling) -> tuple:
    """The rotary pairs between which YaRN ramps from the original to the
    stretched frequency: pairs below ``low`` keep theirs (more than
    ``beta_fast`` rotations over the original length), pairs from ``high``
    on are divided by ``factor`` (fewer than ``beta_slow``)."""
    def dim(rotations):
        return (head_dim * math.log(scaling.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim(scaling.beta_fast)), 0)
    high = min(math.ceil(dim(scaling.beta_slow)), head_dim - 1)
    return low, high


def yarn_inv_freq(head_dim: int, theta: float, scaling):
    """The rotary frequencies under YaRN scaling [head_dim / 2]."""
    low, high = yarn_correction_range(head_dim, theta, scaling)
    extra = rope_freqs(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return extra / scaling.factor * ramp + extra * (1.0 - ramp)


def apply_rope(x, pos, theta: float, scaling=None):
    """x: [..., seq, heads, head_dim] (llama half-rotation), pos: [..., seq].
    ``scaling``: a `configs.base.RopeScaling` (YaRN) or None."""
    d = x.shape[-1]
    freqs = (rope_freqs(d, theta) if scaling is None
             else yarn_inv_freq(d, theta, scaling))  # [d/2]
    angles = pos[..., None].astype(jnp.float32) * freqs  # [..., seq, d/2]
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        cos, sin = cos * m, sin * m
    x1 = x[..., : d // 2].astype(jnp.float32)
    x2 = x[..., d // 2 :].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------- SwiGLU FFN ----------------

def ffn_init(key, d_model: int, d_ff: int, dtype):
    kg, ku, kd = jax.random.split(key, 3)
    return {
        "wg": normal_init(kg, (d_model, d_ff), d_model, dtype),
        "wu": normal_init(ku, (d_model, d_ff), d_model, dtype),
        "wd": normal_init(kd, (d_ff, d_model), d_ff, dtype),
    }


FFN_AXES = {"wg": "embed ff", "wu": "embed ff", "wd": "ff embed"}


def ffn_apply(p, x):
    h = jax.nn.silu(jnp.einsum("...d,df->...f", x, p["wg"]))
    h = h * jnp.einsum("...d,df->...f", x, p["wu"])
    return jnp.einsum("...f,fd->...d", h, p["wd"])


# ---------------- losses ----------------

def next_token_loss(logits, tokens, ignore: int = -100):
    """Causal LM loss: logits[:, t] predicts tokens[:, t+1]. fp32 softmax.

    The correct-class logit is picked with a one-hot contraction rather than
    take_along_axis: a vocab-sharded gather would force XLA to all-gather
    the full fp32 logits (measured in EXPERIMENTS.md §Perf); the contraction
    keeps the vocab axis sharded and reduces to a tiny [B,S] partial sum.
    """
    logits = logits[:, :-1].astype(jnp.float32)
    targets = tokens[:, 1:]
    valid = targets != ignore
    safe_t = jnp.where(valid, targets, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(safe_t, logits.shape[-1], dtype=jnp.float32)
    picked = jnp.einsum("bsv,bsv->bs", logits, onehot)
    nll = jnp.where(valid, lse - picked, 0.0)
    denom = jnp.maximum(valid.sum(), 1)
    return nll.sum() / denom
