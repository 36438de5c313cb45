"""Workflow-DAG routing: precedence-aware IEMAS vs an affinity-blind
graph scheduler.

The ISSUE-7 tentpole measurement.  Both routers drive the same workflow
workloads (`dag_orchestrator` fan-out/fan-in, `dag_handoff` specialist
chains — `repro.serving.workload`) through the event simulator, which
enforces step precedence for either: a step dispatches only after all its
parent steps completed, with the concatenated parent contexts as its
prompt prefix.  The difference under test is *placement*:

  * ``iemas``      — the capacitated-column auction with precedence-aware
                     affinity: `PrefixLedger.parent_credit` folds "this
                     agent holds a PARENT step's KV prefix" into the Eq.-5
                     feature tensor, so handoff steps are co-placed where
                     the producer's cache lives whenever that wins the
                     welfare trade-off.
  * ``graphsched`` — a classic list scheduler over the ready frontier
                     (skill match, then load, then hardware scale;
                     `repro.core.baselines.GraphSchedulerRouter`): it sees
                     the same precedence structure but is blind to cache
                     state, so every handoff re-prefills the carried
                     context from scratch.

Per (family, router) cell it emits::

    dagrouting/<family>_<router>,<wall us>,
        welfare_per_req=..  makespan_s=..  kv=..  ttft_ms=..  cost=..
        done=../..  truncated=..

and per family a comparison line with the IEMAS-over-baseline deltas.
Realized welfare per request is Eq. 1 value at the *observed*
(quality, latency) minus the observed serving cost, averaged over
completed requests; graph makespan is the mean end-to-end dialogue
latency (arrival -> last step completion).

Acceptance gate (asserted under ``--smoke``, run in CI): on BOTH families
IEMAS beats the affinity-blind scheduler on welfare per request AND on
graph makespan, with a higher KV hit rate, and every workflow completes
for both routers.

    PYTHONPATH=src:. python benchmarks/dag_routing.py [--smoke]
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.common import QUICK, emit, start
from repro.core.baselines import GraphSchedulerRouter
from repro.core.valuation import ValuationConfig, client_value
from repro.serving import (EventSimulator, PoissonArrivals, SimCluster,
                           WorkloadSpec, iter_dialogues, make_router)
from repro.serving.workload import DAG_WORKLOADS

N_AGENTS = 12
N_DIALOGUES = 120
SMOKE_DIALOGUES = 40
ARRIVAL_RATE = 12.0


def run_cell(family: str, router_name: str, n_dialogues: int,
             seed: int = 0) -> dict:
    """One (workload family, router) run; adds realized-welfare stats."""
    cluster = SimCluster(n_agents=N_AGENTS, seed=seed, engine_mode="analytic")
    if router_name == "iemas":
        # domain-clustered hubs (§4.4): each step's market is the hub of its
        # skill domain, so online quality prediction starts from sensible
        # candidates and precedence-aware parent_credit co-places handoffs
        # within it (cross-domain handoffs fall back to the spill round)
        router = make_router(cluster, solver="dense", warm_start=True,
                             n_hubs=5)
    else:
        router = GraphSchedulerRouter(cluster.agent_infos(), seed=seed)
    spec = WorkloadSpec(family, n_dialogues=n_dialogues, seed=seed + 1)
    sim = EventSimulator(cluster, router, iter_dialogues(spec),
                         arrivals=PoissonArrivals(rate=ARRIVAL_RATE,
                                                  seed=seed + 2),
                         batch_cap=16, batch_window=0.02, lean=True)
    t0 = time.perf_counter()
    out = sim.run()
    out["bench_wall_s"] = time.perf_counter() - t0
    # realized welfare (Eq. 1 at observed QoS, minus observed cost) — the
    # same definition for both routers, computed from the cluster's own
    # completion records so baseline payments (always 0) don't distort it
    vcfg = ValuationConfig()
    wf = [float(client_value(r.quality, r.latency, vcfg)) - r.cost
          for r in cluster.records]
    out["welfare_per_req"] = float(np.mean(wf)) if wf else 0.0
    out["ttft_mean_ms"] = (1e3 * float(np.mean([r.ttft
                                                for r in cluster.records]))
                           if cluster.records else 0.0)
    return out


def _row(family: str, router_name: str, out: dict) -> None:
    """Emit one CSV row for a (family, router) cell."""
    emit(f"dagrouting/{family}_{router_name}", out["bench_wall_s"] * 1e6,
         f"welfare_per_req={out['welfare_per_req']:.4f} "
         f"makespan_s={out.get('dialogue_latency_mean_s', 0.0):.4f} "
         f"kv={out.get('kv_hit_rate', 0.0):.3f} "
         f"ttft_ms={out['ttft_mean_ms']:.2f} "
         f"cost={out.get('cost_mean', 0.0):.4f} "
         f"done={out.get('dialogues_completed', 0)}"
         f"/{out.get('dialogues_arrived', 0)} "
         f"truncated={out.get('truncated', False)}")


def run(smoke: bool = False):
    """Compare IEMAS vs the affinity-blind graph scheduler per DAG family."""
    n_dialogues = SMOKE_DIALOGUES if (smoke or QUICK) else N_DIALOGUES
    for family in DAG_WORKLOADS:
        cells = {name: run_cell(family, name, n_dialogues)
                 for name in ("iemas", "graphsched")}
        for name, out in cells.items():
            _row(family, name, out)
        iem, base = cells["iemas"], cells["graphsched"]
        mk_i = iem.get("dialogue_latency_mean_s", float("inf"))
        mk_b = base.get("dialogue_latency_mean_s", float("inf"))
        emit(f"dagrouting/{family}_compare", 0.0,
             f"welfare_gain={iem['welfare_per_req'] - base['welfare_per_req']:.4f} "
             f"makespan_speedup={mk_b / max(mk_i, 1e-12):.3f}x "
             f"kv_gain={iem.get('kv_hit_rate', 0) - base.get('kv_hit_rate', 0):.3f}")
        if smoke:
            for name, out in cells.items():
                assert not out["truncated"], f"{family}/{name} truncated"
                assert out["dialogues_completed"] == n_dialogues, \
                    f"{family}/{name}: {out['dialogues_completed']}" \
                    f"/{n_dialogues} workflows completed"
            assert iem["welfare_per_req"] > base["welfare_per_req"], \
                f"{family}: IEMAS welfare/req {iem['welfare_per_req']:.4f} " \
                f"<= affinity-blind {base['welfare_per_req']:.4f}"
            assert mk_i < mk_b, \
                f"{family}: IEMAS makespan {mk_i:.4f}s >= " \
                f"affinity-blind {mk_b:.4f}s"
            assert iem["kv_hit_rate"] > base["kv_hit_rate"], \
                f"{family}: IEMAS kv {iem['kv_hit_rate']:.3f} <= " \
                f"affinity-blind {base['kv_hit_rate']:.3f}"


def main():
    """CLI entry point."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes + win-assertion gates (CI)")
    args = ap.parse_args()
    run(smoke=args.smoke)


if __name__ == "__main__":
    start()
    main()
