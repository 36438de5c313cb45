"""Pallas SSD kernel: chunkwise-parallel Mamba2 recurrence (scalar decay).

Same structure as wkv6.py but with a SCALAR decay per (head, step), so the
intra-chunk decay matrix is [C, C] (not [C, C, dk]) and B/C projections are
shared across heads. State [hd, ds] lives in VMEM scratch across the chunk
grid dimension. All decay exponents relative (<= 0) — overflow-free.

Grid: (B * H, S / C).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

CHUNK = 16


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, la_ref, dskip_ref, y_ref,
                sT_ref, s_ref, *, chunk):
    ic = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0].astype(jnp.float32)       # [C, hd]
    bm = b_ref[0].astype(jnp.float32)      # [C, ds]
    cm = c_ref[0].astype(jnp.float32)      # [C, ds]
    dt = dt_ref[0].astype(jnp.float32)     # [C, 1] -> [C]
    la = la_ref[0].astype(jnp.float32)     # [C, 1]
    dskip = dskip_ref[0, 0, 0]
    dt = dt[:, 0]
    la = la[:, 0]

    c = chunk
    p = jnp.cumsum(la)                     # [C] inclusive
    state = s_ref[...]                     # [hd, ds]

    # intra: M[t,s] = exp(p_t - p_s) * (C_t . B_s) * dt_s, s <= t
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())))  # [C, C]
    ti = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    dec = jnp.exp(jnp.where(si <= ti, p[:, None] - p[None, :], -jnp.inf))
    m = cb * dec * dt[None, :]
    y = jax.lax.dot_general(m, x, (((1,), (0,)), ((), ())))     # [C, hd]

    # inter: y_t += exp(p_t) * (S_in @ C_t)
    y = y + jnp.exp(p)[:, None] * jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())))

    y = y + dskip * x
    y_ref[0] = y.astype(y_ref.dtype)

    # state: S_out = exp(p_last) S_in + sum_s exp(p_last - p_s) dt_s x_s (x) B_s
    w = jnp.exp(p[-1] - p) * dt                                  # [C]
    s_ref[...] = state * jnp.exp(p[-1]) + jax.lax.dot_general(
        x * w[:, None], bm, (((0,), (0,)), ((), ())))

    @pl.when(ic == nc - 1)
    def _emit():
        sT_ref[0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, bmat, cmat, dt, a_log, d_skip, *, chunk: int = CHUNK,
        interpret: bool | None = None):
    """x: [B,S,H,hd]; bmat,cmat: [B,S,ds]; dt: [B,S,H] (post-softplus);
    a_log, d_skip: [H]. Zero initial state. Returns (y, sT [B,H,hd,ds])."""
    b, s, h, hd = x.shape
    ds = bmat.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    ss = s + pad
    nc = ss // chunk

    la = -jnp.exp(a_log.astype(jnp.float32))[None, None, :] * dt  # [B,S',H]
    xx = x.transpose(0, 2, 1, 3).reshape(b * h, ss, hd)
    bb = jnp.broadcast_to(bmat[:, None], (b, h, ss, ds)).reshape(b * h, ss, ds)
    cc = jnp.broadcast_to(cmat[:, None], (b, h, ss, ds)).reshape(b * h, ss, ds)
    dtt = dt.transpose(0, 2, 1).reshape(b * h, ss, 1)
    laa = la.transpose(0, 2, 1).reshape(b * h, ss, 1)
    dsk = jnp.broadcast_to(d_skip.astype(jnp.float32)[None], (b, h)
                           ).reshape(b * h, 1, 1)

    y, sT = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, ds), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, ds), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, 1, 1), lambda bh, ic: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hd), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, hd, ds), lambda bh, ic: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, ss, hd), x.dtype),
            jax.ShapeDtypeStruct((b * h, hd, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, ds), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xx, bb, cc, dtt, laa, dsk)
    y = y.reshape(b, h, ss, hd).transpose(0, 2, 1, 3)
    return y[:, :s], sT.reshape(b, h, hd, ds)
