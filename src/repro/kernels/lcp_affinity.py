"""Pallas kernel: batched longest-common-prefix (the router's affinity hot loop).

The IEMAS proxy computes an N x M LCP matrix per micro-batch (every request
against every agent's prefix ledger, Eq. 4). On TPU there are no divergent
branches for early exit, so the kernel finds the first mismatch with one
masked min-reduction: LCP(a, b) = min_t (t if a_t != b_t else L) — one VPU
pass, no control flow (DESIGN.md §3).

Tiling: grid over (N/bn, M/bm, L/bl). Each program holds a [bn, bl] prompt
tile and a [bn, bm, bl] ledger tile in VMEM and writes a [bn, bm] output
block. The token axis is the innermost (reduction) grid axis: the output
block stays resident across it and each later tile extends the running LCP
only where every earlier token matched (running value == tokens seen so
far). Compiled, the blocks are (8, 128)-aligned: bm = 128 with M padded to
the lane width, bl = 256 with L padded to it — an [8, 128, 256] int32 tile is
1 MiB. In interpret mode (CPU) the plan keeps bm = 8 and one full-width
token tile, so the grid stays small.

``interpret`` follows the kernels' one convention (`repro.kernels`): None
resolves from the backend — compiled Pallas on TPU, interpret mode
everywhere else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

BN = 8          # request rows per tile
BM_INTERPRET = 8
LANE = 128      # agent-axis block (and padding multiple) on real hardware
BL = 256        # token-axis block on real hardware


def _lcp_kernel(p_ref, l_ref, o_ref, *, bl: int):
    k = pl.program_id(2)
    p = p_ref[...]            # [bn, bl]
    led = l_ref[...]          # [bn, bm, bl]
    pos = jax.lax.broadcasted_iota(jnp.int32, led.shape, 2)
    # index of the tile's first mismatch; bl when the whole tile matches
    first = jnp.min(jnp.where(p[:, None, :] == led, bl, pos), axis=-1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = first

    @pl.when(k > 0)
    def _extend():
        prev = o_ref[...]
        o_ref[...] = jnp.where(prev == k * bl, prev + first, prev)


@functools.partial(jax.jit, static_argnames=("interpret",))
def lcp_affinity(prompts, ledgers, *, interpret: bool | None = None):
    """prompts: [N, L] int32; ledgers: [N, M, L] int32 -> lcp [N, M] int32.

    N and M are padded to the block sizes internally (and L to the token
    block when running compiled). ``interpret=None`` resolves backend-aware:
    compiled on TPU, interpret elsewhere.
    """
    interpret = resolve_interpret(interpret)
    n, l = prompts.shape
    m = ledgers.shape[1]
    bm = BM_INTERPRET if interpret else LANE
    bl = l if interpret else BL
    pn = (-n) % BN
    pm = (-m) % bm
    pl_tok = (-l) % bl
    if pn:
        prompts = jnp.pad(prompts, ((0, pn), (0, 0)), constant_values=-1)
        ledgers = jnp.pad(ledgers, ((0, pn), (0, 0), (0, 0)), constant_values=-2)
    if pm:
        ledgers = jnp.pad(ledgers, ((0, 0), (0, pm), (0, 0)), constant_values=-2)
    if pl_tok:
        # pad tokens diverge (-1 vs -2), so the prefix cannot extend past
        # the real width
        prompts = jnp.pad(prompts, ((0, 0), (0, pl_tok)), constant_values=-1)
        ledgers = jnp.pad(ledgers, ((0, 0), (0, 0), (0, pl_tok)),
                          constant_values=-2)
    nn, mm, ll = ledgers.shape

    out = pl.pallas_call(
        functools.partial(_lcp_kernel, bl=bl),
        grid=(nn // BN, mm // bm, ll // bl),
        in_specs=[
            pl.BlockSpec((BN, bl), lambda i, j, k: (i, k)),
            pl.BlockSpec((BN, bm, bl), lambda i, j, k: (i, j, k)),
        ],
        out_specs=pl.BlockSpec((BN, bm), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nn, mm), jnp.int32),
        interpret=interpret,
    )(prompts, ledgers)
    return out[:n, :m]
