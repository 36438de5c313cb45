"""Fused vs staged routing wall-clock, 16 -> 128 agents, one hub.

The ISSUE-9 tentpole measurement: does fusing the whole per-batch routing
step (ledger gather, Eq.-4 LCP affinity, Eq.-5 Hoeffding descent, Eq.-1
values, capacitated-column epsilon-scaling auction) into ONE device-resident
jitted program (`repro.core.routing_fused`) beat the staged pipeline it
mirrors?  For each fleet size the event-driven open-loop simulator runs the
same single-hub warm-started cell three ways::

    fusedrouting/<family>_a<agents>_staged[dense]     host-vectorized oracle
    fusedrouting/<family>_a<agents>_staged[dense-jax] jit-staged, per-stage
    fusedrouting/<family>_a<agents>_fused[dense-jax]  one fused program

Every cell runs TWICE on the same cluster + router: a reduced warmup pass
populates the pow-2 shape-bucket jit caches and the predictor state, then
the full measured pass reports steady-state routing wall-clock per router
invocation (``route_ms=``) so the fused path's one-time XLA compile does
not masquerade as per-batch cost.  Fused rows add the `RoutingProfiler`
spans and counters: ``device=`` calls of the ``fused.device`` span (the
device->host materialization boundary, exactly one per routing step by
construction), ``rounds=`` bid rounds per step and ``retrace=``
measured-pass program cache growth (bounded by the pow-2 buckets the pass
visits, not the batch count).

The sweep closes with a per-family fused/staged routing-time ratio.
``--smoke`` runs one reduced cell with the acceptance gates: fused routing
time <= staged[dense-jax] routing time on the same warmed cell, one device
span per route call, bounded retraces — plus a lockstep fused-vs-staged
decision-parity check over heterogeneous
agents with synchronized feedback (identical assignments, payments within
float32 tolerance; see tests/test_routing_fused.py for the property-test
version).

    PYTHONPATH=src:. python benchmarks/fused_routing.py [--smoke]
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.common import QUICK, emit, start
from repro.configs.iemas_cluster import SCALE_128
from repro.serving import (EventSimulator, PoissonArrivals, RoutingProfiler,
                           SimCluster, WorkloadSpec, iter_dialogues,
                           make_router)
from repro.serving.workload import WORKLOADS

#: same fleet-size grid as benchmarks/serving_scale.py so the routing
#: times line up with its staged-baseline rows
SIZES = [(16, 1000), (32, 2000), (64, 5000),
         (SCALE_128.n_agents, SCALE_128.n_dialogues)]
SMOKE_SIZES = [(16, 150)]
#: measured-pass jit-cache growth bound: the warmup pass visits the common
#: pow-2 buckets, the measured pass may still cross a handful (bigger batch
#: bucket under burstier arrivals, node-pool bucket on forest splits)
RETRACE_BOUND = 16
#: the three comparable single-hub cells per (family, size)
VARIANTS = (("staged[dense]", "dense", False),
            ("staged[dense-jax]", "dense-jax", False),
            ("fused[dense-jax]", "dense-jax", True))


def _sim(cluster, router, family: str, n_dialogues: int, seed: int) -> dict:
    """One profiled simulator pass over a fresh dialogue stream."""
    cfg = SCALE_128
    spec = WorkloadSpec(family, n_dialogues=n_dialogues, seed=seed)
    sim = EventSimulator(cluster, router, iter_dialogues(spec),
                         arrivals=PoissonArrivals(
                             rate=cfg.arrival_rate(len(cluster.agents)),
                             seed=seed + 1),
                         batch_cap=cfg.batch_cap,
                         batch_window=cfg.batch_window,
                         max_inflight=cfg.max_inflight,
                         max_new_tokens=cfg.max_new_tokens,
                         profiler=RoutingProfiler(), lean=True,
                         max_events=20_000_000, max_rounds=2_000_000)
    t0 = time.perf_counter()
    out = sim.run()
    out["bench_wall_s"] = time.perf_counter() - t0
    return out


def run_cell(family: str, n_agents: int, n_dialogues: int, *, solver: str,
             fused: bool, seed: int = 0) -> dict:
    """Warmup pass + measured pass on one single-hub warm-started cell.

    Both passes share the cluster and router so the measured pass sees
    populated jit caches (per pow-2 shape bucket) and warmed predictors —
    the steady-state regime the staged baseline rows were measured in.
    The warmup replays the measured pass's own dialogue stream so the two
    passes visit the same shape buckets.
    """
    cfg = SCALE_128
    cluster = SimCluster(n_agents=n_agents, seed=seed,
                         engine_mode=cfg.engine_mode,
                         max_new_tokens=cfg.max_new_tokens)
    router = make_router(cluster, cfg.router_config(n_agents), solver=solver,
                         n_hubs=1, warm_start=True, fused=fused)
    # full-size warmup on the SAME dialogue stream: a reduced stream never
    # reaches the larger batch-size buckets, so their compiles would land in
    # the measured pass and masquerade as per-batch routing cost
    _sim(cluster, router, family, n_dialogues, seed + 1)
    return _sim(cluster, router, family, n_dialogues, seed + 1)


def _row(tag: str, family: str, n_agents: int, out: dict) -> float:
    """Emit one CSV row; returns the measured pass's routing wall-clock
    per router invocation (ms)."""
    rep = out["routing"]
    phases = rep["phases"]
    route_calls = phases.get("route_batch", {}).get("calls", 0)
    route_ms = 1e3 * phases.get("route_batch", {}).get("wall_s", 0.0) \
        / max(route_calls, 1)
    device = phases.get("fused.device", {}).get("calls", 0)
    rounds = rep["counters"].get("fused.device.rounds", 0)
    cols = [
        f"route_ms={route_ms:.3f}",
        f"routing_s={rep['routing_wall_s']:.2f}",
        f"route_calls={route_calls}",
        f"device={device}",
        f"rounds={rounds / max(device, 1):.1f}",
        f"retrace={rep['counters'].get('fused.device.retraces', 0)}",
        f"n={out.get('n', 0)}",
        f"kv={out.get('kv_hit_rate', 0.0):.3f}",
        f"done={out.get('dialogues_completed', 0)}",
        f"truncated={out.get('truncated', False)}",
    ]
    emit(f"fusedrouting/{family}_a{n_agents}_{tag}",
         out["bench_wall_s"] * 1e6, " ".join(cols))
    return route_ms


def _lockstep_parity(n_batches: int = 6, m: int = 5, seed: int = 1) -> None:
    """Drive a fused and a staged router in lockstep; gate decision parity.

    Heterogeneous per-agent token prices keep the welfare optimum unique —
    under exact column ties the fused float32 welfare matrix and the staged
    float64->float32 one can break ties into different equally-optimal
    permutations (same welfare, same payments), which is tie degeneracy,
    not divergence.  With distinct prices the gate is strict: identical
    assignments every batch, payments within float32 tolerance.
    """
    from repro.core.mechanism import (AgentInfo, CompletionObs, IEMASRouter,
                                      Request)
    from repro.core.pricing import TokenPrices

    rng = np.random.default_rng(seed)

    def agents():
        out = []
        for i in range(m):
            pr = TokenPrices(0.01 * (1 + i / m), 0.001 * (1 + i / m),
                             0.03 * (1 + i / m))
            out.append(AgentInfo(f"a{i}", pr, 2,
                                 ("dialogue",) if i % 2 == 0
                                 else ("dialogue", "reasoning"),
                                 scale=4.0 + i, recurrent=(i == 3),
                                 cache_slots=2 if i == 1 else 0))
        return out

    def batch(n, t):
        brng = np.random.default_rng(seed + 10 + t)
        return [Request(f"r{t}_{j}", f"d{j % 4}",
                        brng.integers(0, 50, int(brng.integers(5, 30))),
                        turn=t, domain="dialogue" if j % 2 == 0
                        else "reasoning")
                for j in range(n)]

    tele = {"router_inflight": 2, "router_rps": 1.0,
            "agent_inflight": {"a0": 1}, "agent_rps": {"a1": 0.5}}
    rs = IEMASRouter(agents(), solver="dense-jax", n_hubs=1, warm_start=True)
    rf = IEMASRouter(agents(), solver="dense-jax", n_hubs=1, warm_start=True,
                     fused=True)
    t0 = time.perf_counter()
    worst = 0.0
    for t in range(n_batches):
        reqs = batch(6, t)
        ds = rs.route_batch(reqs, tele)
        df = rf.route_batch([Request(r.request_id, r.dialogue_id,
                                     r.tokens.copy(), r.turn, r.domain)
                             for r in reqs], tele)
        a_s = [d.agent_id for d in ds]
        a_f = [d.agent_id for d in df]
        assert a_s == a_f, f"batch {t}: fused {a_f} != staged {a_s}"
        pay = np.abs(np.array([d.payment for d in ds])
                     - np.array([d.payment for d in df]))
        worst = max(worst, float(pay.max(initial=0.0)))
        # synchronized feedback keeps both predictor states bit-identical
        for d in ds:
            if d.agent_id:
                obs = CompletionObs(latency=0.03 + 0.01 * rng.random(),
                                    n_prompt=len(d.request.tokens), n_hit=0,
                                    n_gen=20, quality=0.7)
                rs.on_complete(d.request.request_id, obs)
                rf.on_complete(d.request.request_id, obs)
    assert worst < 1e-5, f"payment divergence {worst:.2e} above float32 tol"
    progs = rf._fused.cache_size()
    emit("fusedrouting/lockstep_parity", (time.perf_counter() - t0) * 1e6,
         f"batches={n_batches} agents={m} max_pay_diff={worst:.2e} "
         f"fused_programs={progs}")


def run(smoke: bool = False):
    """Sweep (family x size x variant); gate the smoke cell."""
    quick = smoke or QUICK
    sizes = SMOKE_SIZES if quick else SIZES
    families = WORKLOADS[:1] if quick else WORKLOADS
    for family in families:
        for n_agents, n_dialogues in sizes:
            route_ms = {}
            for tag, solver, fused in VARIANTS:
                out = run_cell(family, n_agents, n_dialogues, solver=solver,
                               fused=fused)
                route_ms[tag] = _row(tag, family, n_agents, out)
                rep = out["routing"]
                assert not out["truncated"], f"{tag} cell truncated"
                device = rep["phases"].get("fused.device", {}).get("calls", 0)
                if fused:
                    route_calls = rep["phases"]["route_batch"]["calls"]
                    retraces = rep["counters"].get("fused.device.retraces", 0)
                    assert device == route_calls, \
                        f"{device} device spans over {route_calls} route " \
                        f"calls (want exactly 1 each)"
                    assert retraces <= RETRACE_BOUND, \
                        f"{retraces} measured-pass retraces > " \
                        f"{RETRACE_BOUND} (pow-2 bucketing regressed?)"
                else:
                    assert device == 0
            if smoke:
                assert route_ms["fused[dense-jax]"] \
                    <= route_ms["staged[dense-jax]"], \
                    f"fused routing {route_ms['fused[dense-jax]']:.3f} ms " \
                    f"above staged {route_ms['staged[dense-jax]']:.3f} ms"
            ratio = (route_ms["fused[dense-jax]"]
                     / max(route_ms["staged[dense-jax]"], 1e-12))
            print(f"fusedrouting/{family}_a{n_agents}_speedup,0.0,"
                  f"fused/staged_route_ms={ratio:.3f} "
                  f"staged_dense_ms={route_ms['staged[dense]']:.3f}",
                  flush=True)
    _lockstep_parity()


def main():
    """CLI entry point."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="one reduced cell + acceptance gates (CI)")
    args = ap.parse_args()
    run(smoke=args.smoke)


if __name__ == "__main__":
    start()
    main()
