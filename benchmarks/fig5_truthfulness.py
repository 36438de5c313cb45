"""Fig. 5: cumulative client utility under bidding strategies over auction
rounds, swept across every registered Phase-2 solver backend.  DSIC
prediction: honest >= every manipulation, every round, on every backend
(the dense-jax float32 path is allowed its certified gap as slack)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import QUICK, emit, start, synthetic_market
from repro.core.auction import client_utilities, run_auction
from repro.core.solvers import available_solvers

STRATEGIES = {
    "honest": lambda v, rng: v,
    "aggressive": lambda v, rng: v * 1.5,
    "conservative": lambda v, rng: v * 0.6,
    "random": lambda v, rng: v * rng.uniform(0.5, 1.5, size=v.shape),
}


def _solvers() -> list[str]:
    """Backends to sweep: every registered solver; QUICK drops the
    interpret-mode pallas kernel (identical mechanism, minutes slower)."""
    names = list(available_solvers())
    if QUICK:
        names = [s for s in names if s != "pallas"]
    return names


def run(rounds: int | None = None, n: int = 12, m: int = 5,
        solvers: list[str] | None = None):
    """Sweep strategies x rounds for each backend; emit one row per
    backend with the final cumulative utilities + the DSIC verdict."""
    rounds = rounds or (40 if QUICK else 100)
    out = {}
    for solver in (solvers or _solvers()):
        rng = np.random.default_rng(7)
        cum = {s: np.zeros(rounds) for s in STRATEGIES}
        for r in range(rounds):
            values, costs, caps, _, _ = synthetic_market(n, m, seed=100 + r)
            for sname, f in STRATEGIES.items():
                reported = values.copy()
                # client 0 is the strategic actor; everyone else truthful
                reported[0] = np.maximum(f(values[0], rng), 0.0)
                res = run_auction(reported, costs, caps, solver=solver)
                u = client_utilities(res, values)[0]
                cum[sname][r] = (cum[sname][r - 1] if r else 0.0) + u
        finals = {s: float(c[-1]) for s, c in cum.items()}
        # float32 backends certify an optimality gap per round; grant it
        slack = 1e-6 if solver in ("mcmf", "dense") else 1e-2
        ok = all(finals["honest"] >= finals[s] - slack for s in STRATEGIES)
        emit(f"fig5/truthfulness/{solver}", 0.0,
             " ".join(f"{s}={v:.2f}" for s, v in finals.items())
             + f" honest_dominates={ok}")
        out[solver] = cum
    return out


if __name__ == "__main__":
    start()
    run()
