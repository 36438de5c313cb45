"""Phase-2 solver comparison: MCMF vs the dense ε-scaling auction backends.

Reports, per problem size (n requests, m agents):
  * wall-clock for the full auction (allocation + VCG payments) under
    - mcmf + naive payments      (N+1 solves; small sizes only)
    - mcmf + warm-start payments (the paper's §4.3 reoptimization)
    - dense ε-scaling auction    (vectorized NumPy + batched Clarke pivots)
    - dense-jax / pallas         (jit-staged bidding loop, pure-jnp vs the
                                  Pallas bidding kernel; steady-state time,
                                  compile excluded; skipped under BENCH_QUICK)
  * the dense solver's welfare gap vs the exact MCMF optimum (should sit at
    float tolerance: the certified bound is 2·n·ε_final).

The n = m = 64 row is the acceptance gate for the dense hot path: dense must
beat the pure-Python MCMF wall-clock by >= 5x.

Large-n backend study (full runs only): at n >= 1k the staged ``pallas``
backend must stay within noise of (or beat) ``dense-jax`` — the two run the
IDENTICAL staged program except for the bidding round, so this isolates the
kernel dispatch cost (interpret mode on CPU; on TPU the same comparison
pits the compiled kernel against XLA's fusion of the jnp round).

Column-market study (ISSUE-6 tentpole): the production solvers bid over
ONE capacitated column per agent (ask = segment-min of the agent's unit
prices) instead of ``min(b_i, n)`` expanded slots, cutting a bidding round
from O(n·K) to O(n·m + K) with ``K = Σ min(b_i, n)`` — a ~K/m round cut in
the slack regime (caps ≫ batch).  ``_column_vs_slot`` measures exactly
that against the retained slot-expanded parity oracle and asserts the
column solve wins wall-clock in the slack regime while certifying the same
welfare as the exact MCMF optimum.

``--smoke`` (CI): reduced sizes plus parity gates — pallas-vs-dense and
column-vs-slot welfare within the summed certificates, payments equal,
column wall-clock no worse than slot-expanded at a K/m ≈ 48 slack cell.
"""
from __future__ import annotations

import argparse
import time

from benchmarks.common import QUICK, emit, start, synthetic_market
from repro.core.auction import run_auction


def _time(fn, repeats=3):
    best, out = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return out, best * 1e6


def _pallas_parity_cols(values, costs, caps, r_dense) -> list[str]:
    """Run the pallas backend and compare against the NumPy dense result."""
    r_pl = run_auction(values, costs, caps, solver="pallas")
    tol = max(1e-6, r_pl.solver_stats["gap_bound"] + 1e-4)
    gap = abs(r_pl.welfare - r_dense.welfare)
    assert gap <= tol, f"pallas welfare gap {gap} > cert {tol}"
    same = r_pl.assignment == r_dense.assignment
    if same:
        pay_gap = max((abs(a - b) for a, b in
                       zip(r_pl.payments, r_dense.payments)), default=0.0)
        assert pay_gap <= 1e-4, f"pallas payment gap {pay_gap}"
    return [f"pallas_welfare_gap={gap:.2e}",
            f"pallas_assignment_match={same}"]


def _column_vs_slot(sizes, assert_speedup: bool = True):
    """Tentpole study: capacitated columns vs per-unit slot expansion.

    Markets are built in the SLACK regime (b_i = n for every agent, so
    K = n·m and K/m = n): this is where the round-cost cut bites.  Gates:

    * welfare parity vs the exact MCMF optimum within each solver's own
      certificate (2·n·ε_final),
    * identical assignments and Clarke payments column-vs-slot,
    * (``assert_speedup``) the column solve's wall-clock beats the
      slot-expanded oracle's.
    """
    import numpy as np

    from repro.core.solvers import get_solver
    from repro.core.solvers.dense_common import package_dense
    from repro.core.solvers.dense_np import (solve_dense_auction,
                                             solve_dense_auction_slots)

    mcmf = get_solver("mcmf")
    for n, m in sizes:
        values, costs, _, _, _ = synthetic_market(n, m, seed=47)
        caps = [n] * m                  # slack regime: K = n*m, K/m = n
        costs64 = np.asarray(costs, dtype=np.float64)
        w = np.maximum(np.asarray(values) - costs64, 0.0)
        r_col, t_col = _time(lambda: solve_dense_auction(w, caps))
        r_slot, t_slot = _time(lambda: solve_dense_auction_slots(w, caps))
        exact = mcmf.solve(w, costs64, caps)
        K = sum(min(int(c), n) for c in caps)
        ratio = t_col / max(t_slot, 1.0)
        gap = abs(r_col.welfare - exact.welfare)
        emit(f"column/n{n}_m{m}_K{K}", t_col,
             f"slot_us={t_slot:.0f} col_us={t_col:.0f} "
             f"col_vs_slot={ratio:.2f}x K_over_m={K / m:.0f} "
             f"welfare_gap_vs_exact={gap:.2e} "
             f"rounds_col={r_col.rounds} rounds_slot={r_slot.rounds}")
        assert gap <= r_col.gap_bound + 1e-6, \
            f"column welfare gap {gap} exceeds certificate {r_col.gap_bound}"
        assert abs(r_slot.welfare - exact.welfare) <= r_slot.gap_bound + 1e-6
        assert r_col.assignment == r_slot.assignment, \
            f"column/slot assignment mismatch at n={n}, m={m}"
        pay_col = package_dense("dense", w, costs64, caps, r_col).payments
        pay_slot = package_dense("dense", w, costs64, caps, r_slot).payments
        pay_gap = max((abs(a - b) for a, b in zip(pay_col, pay_slot)),
                      default=0.0)
        assert pay_gap <= 1e-6, f"column/slot payment gap {pay_gap}"
        if assert_speedup:
            assert ratio < 1.0, \
                f"column solve {ratio:.2f}x of slot-expanded in the slack " \
                f"regime (n={n}, m={m}, K={K}) — expected a win"


def _backend_scaling(sizes=((1024, 128), (2048, 128))):
    """n >= 1k allocation-only study: pallas vs dense-jax, compile excluded.

    Asserts the pallas backend lands within noise of (or beats) dense-jax.
    This runs in FULL benchmark runs only (not under --smoke/BENCH_QUICK,
    so not in CI — CI's --smoke gates correctness parity, not timing); the
    gate uses 2x because this host swings ~±2x run-to-run under load, while
    the committed steady numbers in docs/benchmarks.md straddle 1x.
    """
    import numpy as np

    from repro.core.solvers import (solve_dense_auction_jax,
                                    solve_dense_auction_pallas)

    for n, m in sizes:
        values, costs, caps, _, _ = synthetic_market(n, m, seed=31)
        w = np.maximum(values - costs, 0.0)
        r_jax = solve_dense_auction_jax(w, caps)        # compile once
        r_pl = solve_dense_auction_pallas(w, caps)      # compile once
        _, t_jax = _time(lambda: solve_dense_auction_jax(w, caps), repeats=2)
        _, t_pl = _time(lambda: solve_dense_auction_pallas(w, caps),
                        repeats=2)
        ratio = t_pl / max(t_jax, 1.0)
        gap = abs(r_jax.welfare - r_pl.welfare)
        emit(f"solver_large/n{n}_m{m}", t_pl,
             f"dense_jax_us={t_jax:.0f} pallas_us={t_pl:.0f} "
             f"pallas_vs_jax={ratio:.2f}x welfare_gap={gap:.2e} "
             f"rounds_jax={r_jax.rounds} rounds_pallas={r_pl.rounds}")
        assert gap <= r_pl.gap_bound + 1e-3, \
            f"pallas welfare gap {gap} exceeds certificate"
        assert ratio <= 2.0, \
            f"pallas backend {ratio:.2f}x slower than dense-jax at n={n}"


def run(smoke: bool = False):
    if smoke:
        sizes = [(20, 10), (64, 64)]
    elif QUICK:
        sizes = [(20, 10), (50, 25), (64, 64)]
    else:
        sizes = [(20, 10), (50, 25), (64, 64), (100, 50), (128, 128),
                 (200, 100)]
    for n, m in sizes:
        values, costs, caps, _, _ = synthetic_market(n, m, seed=31)
        r_warm, t_warm = _time(
            lambda: run_auction(values, costs, caps, payment_mode="warmstart"))
        r_dense, t_dense = _time(
            lambda: run_auction(values, costs, caps, solver="dense"))
        gap = abs(r_warm.welfare - r_dense.welfare)
        pay_gap = max(
            (abs(a - b) for a, b in zip(r_warm.payments, r_dense.payments)),
            default=0.0) if r_warm.assignment == r_dense.assignment else -1.0
        cols = [f"warm_us={t_warm:.0f}",
                f"dense_us={t_dense:.0f}",
                f"dense_speedup={t_warm / max(t_dense, 1):.1f}x",
                f"welfare_gap={gap:.2e}",
                f"payment_gap={pay_gap:.2e}" if pay_gap >= 0
                else "payment_gap=n/a(assignment-ties)"]
        if smoke:
            cols += _pallas_parity_cols(values, costs, caps, r_dense)
        if n <= 100 and not smoke:
            # naive is O(N * MCMF); prohibitive past this (the point)
            r_naive, t_naive = _time(
                lambda: run_auction(values, costs, caps, payment_mode="naive"),
                repeats=1)
            same = max(abs(a - b) for a, b in zip(r_naive.payments,
                                                  r_warm.payments)) < 1e-6
            cols += [f"naive_us={t_naive:.0f}",
                     f"warm_vs_naive={t_naive / max(t_warm, 1):.1f}x",
                     f"payments_equal={same}"]
        if not QUICK and not smoke:
            import numpy as np

            from repro.core.solvers import (solve_dense_auction_jax,
                                            solve_dense_auction_pallas)
            w = np.maximum(values - costs, 0.0)
            solve_dense_auction_jax(w, caps)    # compile once
            _, t_jax = _time(lambda: solve_dense_auction_jax(w, caps))
            solve_dense_auction_pallas(w, caps)  # compile once
            _, t_pl = _time(lambda: solve_dense_auction_pallas(w, caps))
            cols += [f"dense_jax_alloc_us={t_jax:.0f}",
                     f"pallas_alloc_us={t_pl:.0f}"]
        emit(f"solver/n{n}_m{m}", t_dense, " ".join(cols))
    if smoke:
        _column_vs_slot([(48, 8)])                 # K/m = 48 slack cell
    elif QUICK:
        _column_vs_slot([(48, 8), (96, 12)])
    else:
        _column_vs_slot([(64, 8), (128, 16), (256, 16)])
        _backend_scaling()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes + pallas parity gates (CI)")
    args = ap.parse_args()
    run(smoke=args.smoke)


if __name__ == "__main__":
    start()
    main()
