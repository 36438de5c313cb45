"""Table 1: system efficiency — KV hit rate, cost, TTFT per router x workload.

Reproduces the paper's Table 1 structure (6 routers x 3 workloads). The
engines run real JAX compute (configs/iemas_cluster.py); quality comes from
the simulated skill matrix (DESIGN.md §8). Expected qualitative result:
IEMAS highest KV %, lowest cost, and lowest/most-competitive latency.
"""
from __future__ import annotations

from benchmarks.common import QUICK, emit, start, timed
from repro.core import IEMASRouter
from repro.core.baselines import BASELINES
from repro.serving import SimCluster, WorkloadSpec, generate, run_workload

ROUTERS = ["iemas", "greedyaffinity", "bandit", "ewmascore", "leastloaded",
           "random"]
WORKLOADS = ["coqa_like", "quac_like", "hotpot_like"]


def run(full: bool = False):
    n_dialogues = 6 if (QUICK and not full) else 12
    n_agents = 4 if (QUICK and not full) else 6
    rows = []
    for wl in WORKLOADS:
        for rname in ROUTERS:
            cluster = SimCluster(n_agents=n_agents, seed=0, max_new_tokens=4,
                                 warmup=True)
            infos = cluster.agent_infos()
            router = (IEMASRouter(infos) if rname == "iemas"
                      else BASELINES[rname](infos, seed=0))
            dialogues = generate(WorkloadSpec(wl, n_dialogues=n_dialogues,
                                              seed=1))
            m, us = timed(run_workload, cluster, router, dialogues,
                          max_rounds=3000)
            rows.append((wl, rname, m))
            emit(f"table1/{wl}/{rname}", us / max(m['n'], 1),
                 f"kv={m['kv_hit_rate']:.3f} cost={m['cost_mean']:.3f} "
                 f"lat_ms={m['latency_ms_median']:.1f} qual={m['quality_mean']:.2f}")
    return rows


if __name__ == "__main__":
    start()
    run(full=True)
