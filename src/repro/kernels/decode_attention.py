"""Pallas flash-decode: one-token attention against a long KV cache.

TPU adaptation of paged/flash-decoding (DESIGN.md §3): pass 1 (the kernel)
splits the cache length M into blocks and emits per-block partial
(max, sum-exp, weighted-V) triples; pass 2 is a tiny jnp log-sum-exp combine.
There is no pointer-chased page table — caches are contiguous slabs and
validity comes from the slot_pos array, which is what the serving layer
maintains anyway.

Grid: (B * Hkv, M / bk). Each program holds the [G, d] query group and one
[bk, d] cache block in VMEM (G = H / Hkv query heads per KV head).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, m_ref, l_ref, acc_ref,
                   *, scale):
    q = q_ref[0].astype(jnp.float32)        # [G, d]
    k = k_ref[0].astype(jnp.float32)        # [bk, d]
    v = v_ref[0].astype(jnp.float32)        # [bk, d]
    valid = valid_ref[0]                    # [bk] int32 (1 = valid)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # [G, bk]
    s = jnp.where(valid[None, :] > 0, s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)                     # [G, 1]
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    acc = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))  # [G, d]
    m_ref[0, 0] = m
    l_ref[0, 0] = l
    acc_ref[0, 0] = acc


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention(q, k_cache, v_cache, valid, *, bk: int = 256,
                     interpret: bool | None = None):
    """q: [B, H, d]; caches: [B, M, Hkv, d]; valid: [B, M] bool -> [B, H, d]."""
    b, h, d = q.shape
    m_len, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    scale = 1.0 / (d ** 0.5)
    bk = min(bk, m_len)
    pm = (-m_len) % bk
    if pm:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pm), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pm), (0, 0), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, pm)))
    mm = m_len + pm
    nk = mm // bk

    qg = q.reshape(b, hkv, g, d).reshape(b * hkv, g, d)
    kk = k_cache.transpose(0, 2, 1, 3).reshape(b * hkv, mm, d)
    vv = v_cache.transpose(0, 2, 1, 3).reshape(b * hkv, mm, d)
    val = jnp.broadcast_to(valid.astype(jnp.int32)[:, None, :],
                           (b, hkv, mm)).reshape(b * hkv, mm)

    m_p, l_p, acc_p = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale),
        grid=(b * hkv, nk),
        in_specs=[
            pl.BlockSpec((1, g, d), lambda bh, ik: (bh, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ik: (bh, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, ik: (bh, ik, 0)),
            pl.BlockSpec((1, bk), lambda bh, ik: (bh, ik)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, 1), lambda bh, ik: (bh, ik, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda bh, ik: (bh, ik, 0, 0)),
            pl.BlockSpec((1, 1, g, d), lambda bh, ik: (bh, ik, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, nk, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * hkv, nk, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * hkv, nk, g, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qg, kk, vv, val)

    # pass 2: combine partials over the nk block axis (log-sum-exp)
    m_all = m_p[..., 0]                       # [BH, nk, G]
    m_star = m_all.max(axis=1, keepdims=True)
    w = jnp.exp(m_all - m_star)               # [BH, nk, G]
    l_tot = (l_p[..., 0] * w).sum(axis=1)     # [BH, G]
    acc = (acc_p * w[..., None]).sum(axis=1)  # [BH, G, d]
    out = acc / jnp.maximum(l_tot, 1e-30)[..., None]
    return out.reshape(b, hkv, g, d).reshape(b, h, d).astype(q.dtype)