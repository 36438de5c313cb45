"""Fig. 4: cumulative social welfare over dialogue turns, IEMAS vs baselines.

Welfare = sum of realized client utility minus agent costs. IEMAS should
hold the steepest trajectory; Random fails to accumulate welfare.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import QUICK, emit, start
from repro.core import IEMASRouter, ValuationConfig, client_value
from repro.core.baselines import BASELINES
from repro.serving import SimCluster, WorkloadSpec, generate, run_workload

ROUTERS = ["iemas", "greedyaffinity", "ewmascore", "random"]


def run():
    n_dialogues = 6 if QUICK else 12
    val = ValuationConfig()
    out = {}
    for rname in ROUTERS:
        cluster = SimCluster(n_agents=5, seed=4, max_new_tokens=4, warmup=True)
        infos = cluster.agent_infos()
        router = (IEMASRouter(infos) if rname == "iemas"
                  else BASELINES[rname](infos, seed=0))
        dialogues = generate(WorkloadSpec("coqa_like", n_dialogues=n_dialogues,
                                          seed=5))
        run_workload(cluster, router, dialogues, max_rounds=3000)
        recs = sorted(cluster.records, key=lambda r: r.dispatched_at)
        w = np.cumsum([float(client_value(r.quality, r.latency, val)) - r.cost
                       for r in recs])
        out[rname] = w
        emit(f"fig4/welfare_{rname}", 0.0,
             f"final={w[-1]:.2f} turns={len(w)}")
    ok = all(out["iemas"][-1] >= out[r][-1] for r in ROUTERS)
    emit("fig4/iemas_leads", 0.0, f"{ok}")
    return out


if __name__ == "__main__":
    start()
    run()
