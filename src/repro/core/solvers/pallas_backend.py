"""``solver="pallas"``: the staged dense auction with a Pallas bidding round.

Same algorithm, schedules and certificates as ``dense-jax`` — the ONLY
difference is the forward bidding round, which runs as the
`repro.kernels.auction_bid` Pallas kernel (per-request top-2 agent profits
against the per-agent ask/ask2 quotes + segment-max scatter of bids into
agent columns, tiled over the (n × m) weight matrix) instead of the
pure-jnp transcription.  Off-TPU the kernel runs in interpret mode
(`repro.kernels.resolve_interpret`), so the backend works — and is tested
bit-for-bit against the jnp oracle — everywhere, while on TPU the bidding
round compiles to a real VMEM-tiled kernel.

Tile plan (backend-aware padding): the column market is zero-padded before
staging — the PR-3 padding argument applies unchanged (a zero-weight row
parks on its first bid; a zero-count agent quotes ask = +big, so it can
neither attract bids nor hold stale units).  On TPU the pad target is the
power-of-two (n, m, cmax) bucket with 128-row tiles, so the
shape-specialized Pallas grid is traced once per bucket (trace reuse
across market-size wobble) and every weight tile stays ≤ 128·m·4 B in
VMEM.  In interpret mode (CPU) per-program overhead dominates and XLA:CPU
column reductions fall off a cache-aliasing cliff when the row stride is a
large power of two, so the plan instead pads minimally — n to one tall
tile of ≤ 1024 rows per grid step, m to a multiple of 8 nudged off
512-multiples — which keeps the kernelized solve within noise of the raw
``dense-jax`` program (`benchmarks/mcmf_scaling`).  The batch path reuses
`solve_dense_auction_jax_batch`'s vmapped pow-2 buckets verbatim with the
kernel swapped in.
"""
from __future__ import annotations

from repro.core.solvers.base import AuctionResult
from repro.core.solvers.dense_common import package_dense
from repro.core.solvers.dense_jax import (solve_dense_auction_jax,
                                          solve_dense_auction_jax_batch)
from repro.core.buckets import pow2_bucket

__all__ = ["solve_dense_auction_pallas", "PallasBackend"]

#: rows per tile in interpret mode; real kernels tile at 128 rows (VMEM)
_TILE_ROWS_INTERPRET = 1024
_TILE_ROWS_TPU = 128


def _tile_split(n: int) -> tuple[int, int]:
    """Interpret-mode (grid, bn) for n rows: the fewest ≤ 1024-row tiles.

    The single source of the tiling invariant: `_pad_plan` pads n to
    ``bn·grid`` and `_bid_round_pallas` re-derives the same (grid, bn)
    from the padded n — ``_tile_split(bn·grid) == (grid, bn)`` by
    construction (bn is a multiple of 8, grid is minimal for it).
    """
    grid = -(-n // _TILE_ROWS_INTERPRET)
    rows = -(-n // grid)                     # ceil(n / grid)
    return grid, max(8, -(-rows // 8) * 8)   # ... rounded up to a mult of 8


def _bid_round_pallas(W, ask, ask2, active, eps):
    """The kernelized forward-bidding round (interpret-mode off TPU).

    The tile height adapts to the (static) padded market: tall tiles
    amortize per-program overhead in interpret mode; 128-row tiles keep
    real TPU weight tiles comfortably inside VMEM.
    """
    from repro.kernels import resolve_interpret
    from repro.kernels.auction_bid import auction_bid

    n = W.shape[0]
    bn = (_tile_split(n)[1] if resolve_interpret(None)
          else min(n, _TILE_ROWS_TPU))
    return auction_bid(W, ask, ask2, active, eps, bn=bn)


def _pad_plan(n: int, m: int, cmax: int, interpret: bool
              ) -> tuple[int, int, int]:
    """Padded (n, m, cmax) for one staged solve (see the module docstring)."""
    if not interpret:
        return pow2_bucket(n), pow2_bucket(m), pow2_bucket(cmax)
    grid, bn = _tile_split(n)
    m_pad = -(-m // 8) * 8
    if m_pad % 512 == 0:
        m_pad += 8          # dodge the pow-2 row-stride aliasing cliff
    return bn * grid, m_pad, cmax


def solve_dense_auction_pallas(w, caps, *, max_rounds: int = 200_000,
                               start_prices=None):
    """Pallas-kernel dense auction solve; returns a DenseAuctionResult.

    Delegates to the shared staged solver with ``bid_round`` swapped for
    the kernel dispatcher and the market padded per the backend-aware tile
    plan (pow-2 shape buckets on TPU, minimal aliasing-safe padding in
    interpret mode).
    """
    import numpy as np

    from repro.core.solvers.dense_common import column_counts

    w = np.asarray(w, dtype=np.float64)
    n, m = w.shape
    counts = column_counts([int(c) for c in caps], n)
    K = int(counts.sum())
    if n and K:
        from repro.kernels import resolve_interpret

        pad = _pad_plan(n, m, int(counts.max()), resolve_interpret(None))
    else:
        pad = None
    return solve_dense_auction_jax(
        w, caps, max_rounds=max_rounds, start_prices=start_prices,
        bid_round=_bid_round_pallas, pad_shape=pad, solver_name="pallas")


class PallasBackend:
    """``solver="pallas"``: staged auction with the Pallas bidding kernel."""

    name = "pallas"
    supports_warm_start = True
    supports_batch = True

    def solve(self, w, costs, caps, *, payment_mode: str = "warmstart",
              start_prices=None) -> AuctionResult:
        """One market through the kernelized staged solver."""
        res = solve_dense_auction_pallas(w, caps, start_prices=start_prices)
        return package_dense(self.name, w, costs, caps, res)

    def solve_batch(self, ws, costs_list, caps_list, *,
                    payment_mode: str = "warmstart", start_prices_list=None
                    ) -> list[AuctionResult]:
        """The vmapped pow-2 bucket batch with the kernel bidding round."""
        dres = solve_dense_auction_jax_batch(
            ws, caps_list, start_prices_list=start_prices_list,
            bid_round=_bid_round_pallas)
        return [package_dense(self.name, w, c, caps, r)
                for w, c, caps, r in zip(ws, costs_list, caps_list, dres)]

    def certificate(self, result: AuctionResult) -> float:
        """2·n·ε_final at the float32 resolution-bounded ε schedule."""
        return float(result.solver_stats["gap_bound"])
