"""Pallas WKV6 kernel: chunkwise-parallel RWKV6 recurrence.

Mirrors models/ssm.wkv6_chunked (same math, same chunk size), with the state
held in a VMEM fp32 scratch that persists across the chunk grid dimension —
the TPU-native replacement for the CUDA sequential-scan kernel (DESIGN.md §3).
All decay exponents are relative (<= 0): no overflow paths.

Grid: (B * H, S / C). Per program: r/k/v/log_w chunk tiles [C, dk] plus the
running state [dk, dv] — with C=16, dk=dv=64 that is ~4*16*64*4 + 64*64*4
= 32 KiB of VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

CHUNK = 16


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, sT_ref, s_ref,
                 *, chunk):
    ic = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)     # [C, dk]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)     # [C, dv]
    lw = lw_ref[0].astype(jnp.float32)   # [C, dk]
    u = u_ref[0].astype(jnp.float32)     # [1, dk] -> broadcast

    p = jnp.cumsum(lw, axis=0)           # inclusive
    p_shift = p - lw                     # exclusive
    state = s_ref[...]

    # inter-chunk
    r_dec = r * jnp.exp(p_shift)
    o = jax.lax.dot_general(r_dec, state, (((1,), (0,)), ((), ())))  # [C, dv]

    # intra-chunk: decay[t,s,d] = exp(p_shift[t,d] - p[s,d]) for s < t
    c = chunk
    ti = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    tri = (si < ti)[:, :, None]
    dec = jnp.exp(jnp.where(tri, p_shift[:, None, :] - p[None, :, :], -jnp.inf))
    a = jnp.einsum("td,sd,tsd->ts", r, k, dec,
                   preferred_element_type=jnp.float32)
    diag = (r * u * k).sum(axis=-1)      # bonus: r_t . (u * k_t)
    a = a + diag[:, None] * jnp.eye(c, dtype=jnp.float32)
    o = o + jax.lax.dot_general(a, v, (((1,), (0,)), ((), ())))
    o_ref[0] = o.astype(o_ref.dtype)

    # state update
    p_last = p[-1:, :]                   # [1, dk]
    k_dec = k * jnp.exp(p_last - p)      # [C, dk]
    s_ref[...] = state * jnp.exp(p_last)[0][:, None] + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())))

    @pl.when(ic == nc - 1)
    def _emit_state():
        sT_ref[0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, log_w, u, *, chunk: int = CHUNK,
         interpret: bool | None = None):
    """r,k,v,log_w: [B,S,H,dk] (dv == dk); u: [H,dk].

    Returns (o [B,S,H,dk], sT [B,H,dk,dk]); initial state is zero (callers
    with a nonzero state fold it in with one extra jnp chunk — the LM path
    uses models/ssm.wkv6_chunked for that case).
    """
    b, s, h, dk = r.shape
    pad = (-s) % chunk
    if pad:
        padfn = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        r, k, v, log_w = padfn(r), padfn(k), padfn(v), padfn(log_w)
    ss = s + pad
    nc = ss // chunk

    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, ss, dk)
    rr, kk, vv, lw = fold(r), fold(k), fold(v), fold(log_w)
    uu = jnp.broadcast_to(u[None], (b, h, dk)).reshape(b * h, 1, dk)

    o, sT = pl.pallas_call(
        functools.partial(_wkv6_kernel, chunk=chunk),
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, dk), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, dk), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, dk), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, 1, dk), lambda bh, ic: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dk), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, dk, dk), lambda bh, ic: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, ss, dk), r.dtype),
            jax.ShapeDtypeStruct((b * h, dk, dk), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dk), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(rr, kk, vv, lw, uu)
    o = o.reshape(b, h, ss, dk).transpose(0, 2, 1, 3)
    return o[:, :s], sT.reshape(b, h, dk, dk)
