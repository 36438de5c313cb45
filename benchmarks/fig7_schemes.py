"""Fig. 7 (Appendix B.1): economics under cluster schemes.

Full-Mix (heterogeneous, no alignment), Ideal (tasks and agents pre-aligned
by domain), Task-Mix (agents clustered, tasks heterogeneous), Agent-Mix
(tasks clustered, agents heterogeneous). Reports welfare, matched fraction,
and IR violations (negative utilities) — the paper finds one-sided
clustering causes congestion and welfare loss.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import QUICK, emit, start, synthetic_market
from repro.core.auction import client_utilities, run_auction


def _pair_welfare(values, costs, caps, r_groups, a_groups):
    welfare, matched, neg = 0.0, 0, 0
    for rg, ag in zip(r_groups, a_groups):
        if not len(rg) or not len(ag):
            continue
        res = run_auction(values[np.ix_(rg, ag)], costs[np.ix_(rg, ag)],
                          [caps[i] for i in ag])
        welfare += res.welfare
        matched += sum(1 for i in res.assignment if i >= 0)
        u = client_utilities(res, values[np.ix_(rg, ag)])
        neg += int((u < -1e-9).sum())
    return welfare, matched, neg


def run(n: int | None = None, m: int | None = None):
    n = n or (60 if QUICK else 120)
    m = m or (30 if QUICK else 60)
    values, costs, caps, req_dom, ag_dom = synthetic_market(n, m, seed=21)
    k = 4
    rng = np.random.default_rng(5)
    dom_r = [np.where(req_dom == d)[0] for d in range(k)]
    dom_a = [np.where(ag_dom == d)[0] for d in range(k)]
    rand_r = np.array_split(rng.permutation(n), k)
    rand_a = np.array_split(rng.permutation(m), k)

    schemes = {
        "fullmix": ([np.arange(n)], [np.arange(m)]),
        "ideal": (dom_r, dom_a),
        "taskmix": (rand_r, dom_a),   # agents clustered, tasks mixed
        "agentmix": (dom_r, rand_a),  # tasks clustered, agents mixed
    }
    w_ref = None
    out = {}
    for name, (rg, ag) in schemes.items():
        w, matched, neg = _pair_welfare(values, costs, caps, rg, ag)
        if name == "fullmix":
            w_ref = w
        out[name] = (w, matched, neg)
        emit(f"fig7/{name}", 0.0,
             f"welfare={w:.1f} frac_of_fullmix={w / max(w_ref, 1e-9):.3f} "
             f"matched={matched} ir_violations={neg}")
    return out


if __name__ == "__main__":
    start()
    run()
