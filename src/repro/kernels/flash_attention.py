"""Pallas flash attention (prefill/training): tiled online-softmax, GQA, SWA.

Layout per program: one (batch*head, q-block) pair iterates over k-blocks in
the innermost grid dimension with fp32 running (m, l, acc) scratch in VMEM —
the canonical TPU flash pattern (no warp shuffles: the combine is a VMEM
reduction, DESIGN.md §3). Block sizes default to 128x128 (MXU-aligned).

GQA is handled in the k/v BlockSpec index maps: query head h reads kv head
h // (H / Hkv) — no repeat-materialization of K/V.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale, bq, bk, seq_k, causal, window):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)          # [bq, d]
    k = k_ref[0].astype(jnp.float32)          # [bk, d]
    v = v_ref[0].astype(jnp.float32)          # [bk, d]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # [bq, bk]

    iq = pl.program_id(1)
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < seq_k
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                              "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128,
                    interpret: bool | None = None):
    """q: [B, Sq, H, d]; k, v: [B, Sk, Hkv, d] -> [B, Sq, H, d]."""
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    scale = 1.0 / (d ** 0.5)

    bq = min(bq, sq)
    bk = min(bk, sk)
    pq = (-sq) % bq
    pk = (-sk) % bk
    qq = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))) if pq else q
    kk = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else k
    vv = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))) if pk else v

    # [B,S,H,d] -> [B*H, S, d] with kv-head folding handled by index maps
    qq = qq.transpose(0, 2, 1, 3).reshape(b * h, sq + pq, d)
    kk = kk.transpose(0, 2, 1, 3).reshape(b * hkv, sk + pk, d)
    vv = vv.transpose(0, 2, 1, 3).reshape(b * hkv, sk + pk, d)

    def kv_index(bh, iq, ik):
        batch = bh // h
        head = bh % h
        return (batch * hkv + head // group, ik, 0)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, bq=bq, bk=bk, seq_k=sk,
                          causal=causal, window=window),
        grid=(b * h, (sq + pq) // bq, (sk + pk) // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq + pq, d), q.dtype),
        scratch_shapes=[
            # fp32 running max / denom / accumulator in VMEM, persistent
            # across the k-block grid dimension
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qq, kk, vv)
    out = out.reshape(b, h, sq + pq, d).transpose(0, 2, 1, 3)
    return out[:, :sq]
