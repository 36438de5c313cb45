"""Grouped SwiGLU expert FFN (Pallas TPU): the dropless MoE's routed experts.

``moe_gmm(x, sizes, wg, wu, wd)`` takes the (token, expert) rows sorted by
expert, ``sizes[e]`` consecutive rows for expert ``e``, and computes each
row's SwiGLU through its own expert's weights in one kernel:

    y[r] = (silu(x[r] @ wg[e]) * (x[r] @ wu[e])) @ wd[e]   for r in group e

Rows past ``sum(sizes)`` belong to no group and read as zeros.

The grid walks only the (row tile, expert) pairs that hold rows: a group
that straddles row tiles visits each of them, and an expert that got no
rows is never visited, so its weight tiles are never loaded. That is what
makes a decode step read ``top_k`` experts' weights instead of all of them.
The visit count is data-dependent and is the grid's (dynamic) first axis;
the second walks the expert width in ``tf``-wide slices, accumulating the
down projection in float32. Consecutive visits of one row tile keep its
output block resident, and each visit stores only its own group's rows.

The weights may carry a leading layer axis (a model's stacked layers) and
a traced ``layer`` index: the kernel reads that layer's tiles in place, so
no per-layer copy of the expert weights is made for it.

The kernel is named ``moe_gmm`` in the device trace. ``moe_gmm_ref`` in
``ref.py`` is its oracle, and its backward pass (the kernel has no
autodiff rule of its own).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

#: the kernel's name in the device trace
NAME = "moe_gmm"
#: largest row tile; smaller calls (a decode step's ``top_k`` rows) take
#: one tile of their own size, rounded up to the bfloat16 sublane tile
TM_MAX = 128
SUBLANE = 16
LANE = 128


def row_tile(rows: int) -> int:
    """The row tile for ``rows`` sorted rows (static)."""
    return min(TM_MAX, -(-rows // SUBLANE) * SUBLANE)


def visits(sizes, rows: int, tm: int):
    """The grid's work list: for each (row tile, expert) visit, in order of
    expert, the expert and the row tile, padded to the static bound
    ``tiles + experts - 1``; and the number of visits (traced).

    Returns (group_ids, tile_ids, offsets, n_visits); ``offsets`` [E + 1]
    are the groups' first rows."""
    e = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    # tiles that group g touches: start // tm .. (end - 1) // tm
    count = jnp.where(sizes > 0, (ends - 1) // tm - starts // tm + 1, 0)
    first = jnp.cumsum(count) - count
    bound = rows // tm + e - 1
    v = jnp.arange(bound, dtype=jnp.int32)
    gid = jnp.clip(jnp.searchsorted(jnp.cumsum(count), v, side="right"),
                   0, e - 1).astype(jnp.int32)
    tile = (starts[gid] // tm + v - first[gid]).astype(jnp.int32)
    tile = jnp.clip(tile, 0, rows // tm - 1)
    return gid, tile, offsets.astype(jnp.int32), count.sum().astype(jnp.int32)


def _kernel(layer_ref, gid_ref, tile_ref, off_ref, x_ref, wg_ref, wu_ref,
            wd_ref, out_ref, acc_ref, *, tm: int, nf: int):
    i, f = pl.program_id(0), pl.program_id(1)

    @pl.when(f == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    acc_ref[...] += jnp.dot(h, wd_ref[...], preferred_element_type=jnp.float32)

    @pl.when(f == nf - 1)
    def _store():
        e = gid_ref[i]
        row = (jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
               + tile_ref[i] * tm)
        mine = (row >= off_ref[e]) & (row < off_ref[e + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...],
                                 out_ref[...].astype(jnp.float32)
                                 ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_gmm(x, sizes, wg, wu, wd, layer=None, *,
            interpret: bool | None = None):
    """x: [R, D] rows sorted by expert; sizes: [E] int32 rows per expert
    (``sum(sizes) <= R``); wg, wu: [E, D, F]; wd: [E, F, D], or each with
    a leading layer axis and ``layer`` (int32) the layer to use. Returns
    [R, D] in x's dtype, zeros past ``sum(sizes)``."""
    interpret = resolve_interpret(interpret)
    if layer is None:
        wg, wu, wd = wg[None], wu[None], wd[None]
        layer = 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    r, d = x.shape
    f = wg.shape[3]
    tm = row_tile(r)
    rp = -(-r // tm) * tm
    tf = LANE if f % LANE == 0 else f
    nf = f // tf
    xp = jnp.pad(x, ((0, rp - r), (0, 0)))
    gid, tile, offsets, n_visits = visits(sizes.astype(jnp.int32), rp, tm)
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, nf=nf),
        out_shape=jax.ShapeDtypeStruct((rp, d), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_visits, nf),
            in_specs=[
                pl.BlockSpec((tm, d), lambda i, j, l, g, t, o: (t[i], 0)),
                pl.BlockSpec((None, None, d, tf),
                             lambda i, j, l, g, t, o: (l[0], g[i], 0, j)),
                pl.BlockSpec((None, None, d, tf),
                             lambda i, j, l, g, t, o: (l[0], g[i], 0, j)),
                pl.BlockSpec((None, None, tf, d),
                             lambda i, j, l, g, t, o: (l[0], g[i], j, 0)),
            ],
            out_specs=pl.BlockSpec((tm, d),
                                   lambda i, j, l, g, t, o: (t[i], 0)),
            scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=NAME,
    )(layer, gid, tile, offsets, xp, wg, wu, wd)
    # rows of no group were never stored
    live = jnp.arange(rp) < offsets[-1]
    return jnp.where(live[:, None], out, 0)[:r]
