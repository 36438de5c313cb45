"""Plain reference for the router's Phase 2: exact allocation and Clarke
payments of one routed market.

The market is the welfare matrix ``w = max(values - costs, 0)`` of n
requests over m agents, each agent with ``caps[i]`` free units. The exact
welfare-maximising allocation is a b-matching, solved here as one
assignment problem (agent i's units become ``min(caps[i], n)`` identical
columns, and each request has an outside option of welfare 0) by SciPy's
exact ``linear_sum_assignment``, in float64. It imports nothing of the
program.

Clarke (VCG) payments: request j matched to agent i pays
``p_j = c_ji + w_ji - (W - W_-j)``, where ``W`` is the optimal welfare and
``W_-j`` the optimal welfare without request j (one more exact solve).
``W - W_-j`` is j's marginal contribution, which is the same in every
optimal allocation, so the payment is well defined for whichever optimal
agent the router chose.

``check_market`` compares one routed market with the reference and returns
the numbers that decide ``correct``:

* ``over_capacity``: units assigned beyond an agent's free capacity;
* ``welfare_gap``: (W - W_router) / W, the router's shortfall;
* ``payment_err``: the largest |p_router - p_ref| over matched requests, as
  a share of max(|p_ref|, the market's median |p_ref|).

``check_market(..., quant="bf16")`` is the control: the reference itself,
fed values and costs rounded to bfloat16 (one precision step below the
router's float32) and read by the same numbers against the float64 truth.
"""
from __future__ import annotations

import numpy as np
from scipy.optimize import linear_sum_assignment


def bf16(x: np.ndarray) -> np.ndarray:
    """Round float64 to bfloat16 (round to nearest even), back to float64."""
    f = np.asarray(x, np.float32)
    u = f.view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


def _solve(w: np.ndarray, caps) -> tuple:
    """Optimal welfare and allocation (agent index or -1 per request)."""
    n, m = w.shape
    if n == 0:
        return 0.0, []
    cols = [i for i in range(m) for _ in range(min(int(caps[i]), n))]
    big = np.zeros((n, len(cols) + n))
    if cols:
        big[:, : len(cols)] = w[:, cols]
    rows, picked = linear_sum_assignment(big, maximize=True)
    alloc = [-1] * n
    total = 0.0
    for j, c in zip(rows, picked):
        if c < len(cols) and w[j, cols[c]] > 0.0:
            alloc[j] = cols[c]
            total += w[j, cols[c]]
    return total, alloc


def clarke(values, costs, caps) -> dict:
    """Exact allocation, welfare and each request's marginal contribution."""
    values = np.asarray(values, np.float64)
    costs = np.asarray(costs, np.float64)
    w = np.maximum(values - costs, 0.0)
    total, alloc = _solve(w, caps)
    marginal = np.zeros(len(alloc))
    for j, i in enumerate(alloc):
        if i >= 0:
            without, _ = _solve(np.delete(w, j, axis=0), caps)
            marginal[j] = total - without
    return {"w": w, "costs": costs, "welfare": total, "alloc": alloc,
            "marginal": marginal}


def _payments(ref: dict, alloc) -> np.ndarray:
    w, c, mc = ref["w"], ref["costs"], ref["marginal"]
    return np.array([c[j, i] + w[j, i] - mc[j] if i >= 0 else 0.0
                     for j, i in enumerate(alloc)])


def check_market(values, costs, caps, alloc, payments,
                 quant: str = "none") -> dict:
    """The router's allocation/payments (or, with ``quant="bf16"``, the
    control's own) against the exact float64 reference."""
    truth = clarke(values, costs, caps)
    w = truth["w"]
    if quant == "bf16":
        ctl = clarke(bf16(values), bf16(costs), caps)
        alloc = ctl["alloc"]
        payments = _payments(ctl, alloc)
    elif quant != "none":
        raise ValueError(f"unknown control {quant!r}")
    alloc = [int(i) for i in alloc]
    used = np.bincount([i for i in alloc if i >= 0], minlength=w.shape[1])
    over = int(np.maximum(used - np.asarray(caps, np.int64), 0).sum())
    got = float(sum(w[j, i] for j, i in enumerate(alloc) if i >= 0))
    gap = (truth["welfare"] - got) / truth["welfare"] \
        if truth["welfare"] > 0 else 0.0
    ref_pay = _payments(truth, alloc)
    matched = [j for j, i in enumerate(alloc) if i >= 0]
    err = 0.0
    if matched:
        scale = max(float(np.median(np.abs(ref_pay[matched]))), 1e-12)
        for j in matched:
            d = abs(float(payments[j]) - ref_pay[j])
            err = max(err, d / max(abs(ref_pay[j]), scale))
    return {"over_capacity": over, "welfare_gap": gap, "payment_err": err}
