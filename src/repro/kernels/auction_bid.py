"""Pallas kernel: one Jacobi forward-bidding round of the column auction.

The Phase-2 ε-scaling auction (`repro.core.solvers`) spends almost all of
its time in the forward bidding round.  Since PR 6 the market holds ONE
capacitated column per agent (m columns) instead of one column per unit
slot (K = Σ min(b_i, n) columns): the solver keeps an (m × cmax) unit-price
grid and hands this kernel the two cheapest unit prices per agent — the
segment-min ``ask`` and the second-cheapest ``ask2``.  The kernel computes
one bidding round for an (n × m) agent-level weight matrix:

    P[j, i]  = W[j, i] - ask[i]               (only active rows compete)
    v1, k1   = top profit and its agent       (per request)
    v2       = runner-up profit with the favourite agent's own ask2
               substituted at k1, floored at the outside option 0
    bid[j]   = ask[k1] + (v1 - v2) + ε        (only if v1 > 0, else park)
    best[i]  = max over bidders with k1 = i of bid[j]   (segment max)
    winner[i]= min j among bidders at best[i]           (deterministic ties)

The ask2 substitution is what makes the aggregated column equivalent to a
slot-expanded market: a request whose top TWO profits both sit at the same
agent would, under slot expansion, see that agent's two cheapest slots as
two distinct columns — here the second one re-enters through ask2.

Tiling
------
Grid over request tiles: ``(n / bn,)`` programs, each holding a [bn, m]
weight tile, the full [1, m] ask/ask2 rows and a [bn, 1] active mask in
VMEM (agents are NOT tiled — m is the per-hub agent count, far below the
old K slot count in the slack regime).  The per-request outputs
(``wants``) block-map one tile per program; the per-agent outputs
(``best``, ``winner``) map every program onto the SAME [1, m] block,
exploiting the sequential grid execution on a TPU core: each program folds
its tile's segment max into the accumulator (max for bids, three-way merge
for the tie-broken winner), with ``pl.when(i == 0)`` initialization.  With
bn = 8 and m = 4096 float32 the working set is 8·4096·4 B ≈ 128 KiB —
comfortably inside a v5e core's VMEM, and the scatter never leaves the
tile (the one-hot trick: a segment max over k1 is a masked row-max, no
gather/scatter primitives needed).

The caller pads n to the tile size and m to the lane width; padded rows
are inactive and padded agents carry weight 0 at ask = ask2 = +big (an
agent with no units quotes an infinite ask), so neither can attract or
place a bid.  ``kernels/ref.py::auction_bid_ref`` is the pure jnp oracle;
the interpret-mode kernel is bit-identical to it (same op order; max/argmax
reductions are order-independent, the one-hot ask gathers add exact zeros).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

BN = 8          # request rows per tile
LANE = 128      # agent-dimension padding multiple on real hardware


def _bid_kernel(w_ref, a1_ref, a2_ref, act_ref, e_ref,
                best_ref, win_ref, wants_ref, *, n_total: int, bn: int):
    i = pl.program_id(0)
    W = w_ref[...]                       # [bn, m] agent-level weights
    ask = a1_ref[...]                    # [1, m] cheapest unit per agent
    ask2 = a2_ref[...]                   # [1, m] second-cheapest unit
    act = act_ref[...] != 0              # [bn, 1]
    eps = e_ref[0, 0]
    m = W.shape[1]
    big = jnp.asarray(jnp.finfo(W.dtype).max / 4, W.dtype)

    P = jnp.where(act, W - ask, -big)                        # [bn, m]
    v1 = P.max(axis=1)
    k1 = P.argmax(axis=1)
    onehot = jax.lax.broadcasted_iota(jnp.int32, (bn, m), 1) == k1[:, None]
    # the favourite agent's column re-enters the runner-up scan at its own
    # second-cheapest unit — the collapsed image of the next slot
    alt = jnp.where(onehot & act, W - ask2, P)
    v2 = jnp.maximum(alt.max(axis=1), 0.0)
    wants = act[:, 0] & (v1 > 0.0)
    # ask[k1] as a masked sum: exactly one nonzero term, so bit-exact
    a_k1 = jnp.where(onehot, ask, 0.0).sum(axis=1)
    bid = a_k1 + (v1 - v2) + eps

    # segment max of bids into agent columns, entirely within the tile
    contrib = jnp.where(onehot & wants[:, None], bid[:, None], -big)
    tile_best = contrib.max(axis=0)                          # [m]
    rowid = i * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, m), 0)
    cand = jnp.where((contrib == tile_best[None, :]) & (contrib > -big),
                     rowid, n_total)
    tile_win = cand.min(axis=0).astype(jnp.int32)            # [m]

    wants_ref[...] = wants[:, None].astype(jnp.int32)

    @pl.when(i == 0)
    def _init():
        best_ref[...] = tile_best[None, :]
        win_ref[...] = tile_win[None, :]

    @pl.when(i > 0)
    def _fold():
        prev_best = best_ref[0, :]
        prev_win = win_ref[0, :]
        # ties to the lowest request index; earlier tiles hold lower rows,
        # so equality keeps the accumulated winner via min
        best_ref[...] = jnp.maximum(prev_best, tile_best)[None, :]
        win_ref[...] = jnp.where(
            tile_best > prev_best, tile_win,
            jnp.where(tile_best < prev_best, prev_win,
                      jnp.minimum(prev_win, tile_win)))[None, :]


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def auction_bid(W, ask, ask2, active, eps, *, bn: int = BN,
                interpret: bool | None = None):
    """One Jacobi forward-bidding round over agent-level weights.

    ``W``: [n, m] non-negative weights; ``ask``/``ask2``: [m] cheapest and
    second-cheapest unit price per agent (+big where the agent has fewer
    than one/two free-or-filled units); ``active``: [n] bool (unassigned,
    not parked); ``eps`` scalar.  Returns ``(best, winner, wants)``: the
    per-agent segment-max bid [m] (−big where no bid), the winning request
    per agent [m] int32 (n where none), and the per-request wants-to-bid
    mask [n] bool (active rows with positive top profit; active rows with
    ``~wants`` park on the outside option).

    n is padded to the tile size (and m to the lane width off-interpret)
    internally; callers that pre-pad to power-of-two shape buckets hit a
    single trace across batch-size wobble.
    """
    interpret = resolve_interpret(interpret)
    W = jnp.asarray(W)
    n, m = W.shape
    pn = (-n) % bn
    pm = 0 if interpret else (-m) % LANE
    big = jnp.asarray(jnp.finfo(W.dtype).max / 4, W.dtype)
    if pn:
        W = jnp.pad(W, ((0, pn), (0, 0)))
        active = jnp.pad(jnp.asarray(active), (0, pn))
    if pm:
        # padded agents: weight 0 at ask/ask2 +big -> profit is hugely
        # negative, so they can never be a request's top-2 nor take a bid
        W = jnp.pad(W, ((0, 0), (0, pm)))
        ask = jnp.pad(jnp.asarray(ask), (0, pm), constant_values=big)
        ask2 = jnp.pad(jnp.asarray(ask2), (0, pm), constant_values=big)
    nn, mm = W.shape

    best, winner, wants = pl.pallas_call(
        functools.partial(_bid_kernel, n_total=nn, bn=bn),
        grid=(nn // bn,),
        in_specs=[
            pl.BlockSpec((bn, mm), lambda i: (i, 0)),
            pl.BlockSpec((1, mm), lambda i: (0, 0)),
            pl.BlockSpec((1, mm), lambda i: (0, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, mm), lambda i: (0, 0)),
            pl.BlockSpec((1, mm), lambda i: (0, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, mm), W.dtype),
            jax.ShapeDtypeStruct((1, mm), jnp.int32),
            jax.ShapeDtypeStruct((nn, 1), jnp.int32),
        ],
        interpret=interpret,
    )(W,
      jnp.asarray(ask, W.dtype).reshape(1, mm),
      jnp.asarray(ask2, W.dtype).reshape(1, mm),
      jnp.asarray(active, jnp.int32).reshape(nn, 1),
      jnp.asarray(eps, W.dtype).reshape(1, 1))
    # padded rows never bid, so any no-winner sentinel folds back to n
    return (best[0, :m], jnp.minimum(winner[0, :m], n),
            wants[:n, 0].astype(bool))
