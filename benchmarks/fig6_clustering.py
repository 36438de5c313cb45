"""Fig. 6: clustering trade-off — social welfare & solver time vs number of
proxy hubs K (paper: M=100 agents, N=200 tasks; sharp solver-time drop with
marginal welfare loss)."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import QUICK, emit, start, synthetic_market
from repro.core.auction import run_auction
from repro.core.hub import cluster_agents


def run(n: int | None = None, m: int | None = None):
    n = n or (80 if QUICK else 200)
    m = m or (40 if QUICK else 100)
    values, costs, caps, req_dom, ag_dom = synthetic_market(n, m, seed=11)
    agent_domains = [(f"dom{d}",) for d in ag_dom]
    results = []
    for k in (1, 2, 4, 8, 16):
        hubs = cluster_agents(agent_domains, [1.0] * m, k, scheme="domain")
        t0 = time.perf_counter()
        # coarse stage: every request lands in exactly ONE hub; hubs publish
        # free capacity so the classifier spills when a hub saturates (§4.4)
        remaining = [sum(caps[i] for i in hub.agent_indices) for hub in hubs]
        hub_of_req = []
        for j in range(n):
            scores = []
            for h, hub in enumerate(hubs):
                match = sum(1 for i in hub.agent_indices
                            if ag_dom[i] == req_dom[j])
                scores.append((match / max(len(hub.agent_indices), 1)
                               + (0.0 if remaining[h] > 0 else -10.0), h))
            h = max(scores)[1]
            hub_of_req.append(h)
            remaining[h] -= 1
        welfare = 0.0
        for h, hub in enumerate(hubs):
            a_idx = hub.agent_indices
            r_idx = [j for j in range(n) if hub_of_req[j] == h]
            if not r_idx or not a_idx:
                continue
            res = run_auction(values[np.ix_(r_idx, a_idx)],
                              costs[np.ix_(r_idx, a_idx)],
                              [caps[i] for i in a_idx])
            welfare += res.welfare
        dt = (time.perf_counter() - t0) * 1e6
        results.append((k, welfare, dt))
    w1 = results[0][1]
    for k, w, dt in results:
        emit(f"fig6/clusters_k{k}", dt,
             f"welfare={w:.1f} welfare_frac={w / max(w1, 1e-9):.3f}")
    return results


if __name__ == "__main__":
    start()
    run()
