"""Serving-scale sweep: routing wall-clock per batch, 16 -> 128 agents.

The scale measurement (ROADMAP: "scale the serving simulation to
100+ agents / 10k dialogues and profile the router").  For each workload
family the event-driven open-loop simulator
(`repro.serving.simulator.EventSimulator`) drives a Poisson dialogue stream
through an analytic-engine cluster while a `RoutingProfiler` times the
router's real wall-clock per phase (Phase-1 predict, Phase-2 solve per
backend, cross-hub spill, price-book ops, Phase-4 feedback).  Routing time
is reported in absolute milliseconds, per router invocation (per
completion for feedback), not as a share of the analytic engines' virtual
seconds: a faster engine must not read as a slower router.  Per cell it
emits::

    servingscale/<family>_a<agents>_d<dialogues>,<wall us>,
        route_ms=..  p1_ms=..  p2_ms=..  spill_ms=..  book_ms=..
        fb_us=..  routing_s=..  route_calls=..  n=..  kv=..  ...

Pass ``--oracle`` to add an exact-MCMF row at the smallest size: at
micro-batch markets even the Python oracle is affordable — its blowup is
market-size-driven (`mcmf_scaling.py`), which is exactly what hub sharding
keeps bounded.

Acceptance gate: the full run completes the 128-agent / 10k-dialogue cell
per family (all dialogues finish, nothing truncated).  ``--smoke`` runs one
reduced cell with structural gates for CI.

    PYTHONPATH=src:. python benchmarks/serving_scale.py [--smoke] [--oracle]
"""
from __future__ import annotations

import argparse
import time

from benchmarks.common import QUICK, emit, start
from repro.configs.iemas_cluster import SCALE_1K, SCALE_128
from repro.serving import (EventSimulator, PoissonArrivals, RoutingProfiler,
                           SimCluster, WorkloadSpec, build_federation,
                           iter_dialogues, make_router)
from repro.serving.workload import WORKLOADS

#: (n_agents, n_dialogues) sweep — dialogues scale with the fleet so every
#: cell runs a comparable virtual-time window at the SCALE_128 per-agent
#: arrival rate; the last entry is the headline SCALE_128 cell itself
SIZES = [(16, 1000), (32, 2000), (64, 5000),
         (SCALE_128.n_agents, SCALE_128.n_dialogues)]
SMOKE_SIZES = [(16, 150)]
#: smoke regression bound on the routing wall-clock per router invocation
#: (ms): an order of magnitude above the smoke cell's measured time on a
#: CPU container, so it catches a blowup, not noise
ROUTE_MS_BOUND = 150.0
#: the federated smoke cell's bound on routing wall-clock per completed
#: request (us), with the same headroom
FED_ROUTE_US_BOUND = 30_000.0

#: federation study grid: (n_agents, n_dialogues, super_hubs); the first
#: cell — the single-heap sweep's flagship 128 × 10k size — also runs
#: S=1 for the welfare/overhead comparison, and the last entry is the
#: SCALE_1K headline (1024 agents, 100k dialogues, 8 super-hub shards in
#: their own OS processes)
FED_SIZES = [(128, 10_000, 4),
             (SCALE_1K.n_agents, SCALE_1K.n_dialogues, SCALE_1K.super_hubs)]
FED_SMOKE = [(32, 300, 4)]


def run_cell(family: str, n_agents: int, n_dialogues: int, *,
             solver: str | None = None, seed: int = 0,
             incremental: bool = False) -> dict:
    """One sweep cell at the `SCALE_128` preset knobs (fleet size varies)."""
    cfg = SCALE_128
    cluster = SimCluster(n_agents=n_agents, seed=seed,
                         engine_mode=cfg.engine_mode,
                         max_new_tokens=cfg.max_new_tokens)
    router = make_router(cluster, cfg.router_config(n_agents),
                         **({"solver": solver} if solver else {}))
    spec = WorkloadSpec(family, n_dialogues=n_dialogues, seed=seed + 1)
    sim = EventSimulator(cluster, router, iter_dialogues(spec),
                         arrivals=PoissonArrivals(
                             rate=cfg.arrival_rate(n_agents), seed=seed + 2),
                         batch_cap=cfg.batch_cap,
                         batch_window=cfg.batch_window,
                         incremental=incremental,
                         max_inflight=cfg.max_inflight,
                         max_new_tokens=cfg.max_new_tokens,
                         profiler=RoutingProfiler(), lean=True,
                         max_events=20_000_000, max_rounds=2_000_000)
    t0 = time.perf_counter()
    out = sim.run()
    out["bench_wall_s"] = time.perf_counter() - t0
    out["accounts"] = dict(router.accounts)
    return out


def _ms_per(report: dict, prefix: str, per: str = "route_batch") -> float:
    """Summed wall-clock (ms) of the phases starting with ``prefix``, per
    call of the ``per`` phase (0 when it never ran)."""
    calls = report["phases"].get(per, {}).get("calls", 0)
    wall = sum(p["wall_s"] for name, p in report["phases"].items()
               if name.startswith(prefix))
    return 1e3 * wall / calls if calls else 0.0


def _wall(report: dict, name: str) -> float:
    """Wall-clock seconds of one phase (0 when it never ran)."""
    return report["phases"].get(name, {}).get("wall_s", 0.0)


def _us_per_req(out: dict) -> float:
    """Routing wall-clock (router invocations, feedback and, federated,
    the epoch boundaries) per completed request, in microseconds."""
    return 1e6 * out["routing"]["routing_wall_s"] / max(out.get("n", 0), 1)


def _row(family: str, n_agents: int, n_dialogues: int, out: dict) -> float:
    """Emit one CSV row; returns the routing wall-clock per router
    invocation (ms)."""
    rep = out["routing"]
    route_ms = _ms_per(rep, "route_batch")
    cols = [
        f"route_ms={route_ms:.3f}",
        f"p1_ms={_ms_per(rep, 'phase1_predict'):.3f}",
        f"p2_ms={_ms_per(rep, 'phase2_solve'):.3f}",
        f"spill_ms={_ms_per(rep, 'phase2_spill'):.3f}",
        f"book_ms={_ms_per(rep, 'price_book'):.4f}",
        f"fb_us={1e3 * _ms_per(rep, 'phase4_feedback', 'phase4_feedback'):.1f}",
        f"routing_s={rep['routing_wall_s']:.2f}",
        f"route_calls={rep['phases'].get('route_batch', {}).get('calls', 0)}",
        f"n={out.get('n', 0)}",
        f"kv={out.get('kv_hit_rate', 0.0):.3f}",
        f"lat_p95_ms={out.get('latency_ms_p95', 0.0):.1f}",
        f"wait_ms={1e3 * out.get('queue_wait_mean_s', 0.0):.1f}",
        f"done={out.get('dialogues_completed', 0)}"
        f"/{out.get('dialogues_arrived', 0)}",
        f"truncated={out.get('truncated', False)}",
    ]
    emit(f"servingscale/{family}_a{n_agents}_d{n_dialogues}",
         out["bench_wall_s"] * 1e6, " ".join(cols))
    return route_ms


def _incremental_study(family: str, n_agents: int, n_dialogues: int,
                       gate: bool) -> None:
    """ISSUE-6 tentpole measurement: incremental vs batch-window routing.

    Runs the same cell twice — batch-only and ``incremental=True`` (newly
    ready work bids into the standing duals and dispatches immediately;
    the next batch auction re-equilibrates) — and emits the arrival-latency
    comparison.  Gates (``gate``): provisional routing actually fired, the
    mean queue wait drops BELOW the batch-window latency floor, and the
    realized per-request welfare holds within 10% — greedy posted-price
    dispatch trades a few percent of welfare (measured ~5% at the smoke
    cell) for the latency win; the next batch auction re-equilibrates the
    duals so the loss does not compound.
    """
    cfg = SCALE_128
    base = run_cell(family, n_agents, n_dialogues)
    inc = run_cell(family, n_agents, n_dialogues, incremental=True)
    wait_b = base.get("queue_wait_mean_s", 0.0)
    wait_i = inc.get("queue_wait_mean_s", 0.0)
    wf_b = base["accounts"]["welfare_realized"] / max(base.get("n", 1), 1)
    wf_i = inc["accounts"]["welfare_realized"] / max(inc.get("n", 1), 1)
    frac = inc["incremental_dispatched"] / max(inc["dispatched_requests"], 1)
    confirmed = inc["accounts"]["incremental_confirmed"]
    rerouted = inc["accounts"]["incremental_rerouted"]
    emit(f"servingscale/{family}_a{n_agents}_incremental",
         inc["bench_wall_s"] * 1e6,
         f"wait_batch_ms={1e3 * wait_b:.2f} wait_inc_ms={1e3 * wait_i:.2f} "
         f"window_ms={1e3 * cfg.batch_window:.0f} "
         f"inc_frac={frac:.2f} confirmed={confirmed} rerouted={rerouted} "
         f"welfare_per_req_batch={wf_b:.4f} welfare_per_req_inc={wf_i:.4f}")
    if gate:
        assert inc["incremental_dispatched"] > 0, "no provisional dispatches"
        assert not inc["truncated"]
        assert inc["dialogues_completed"] == n_dialogues
        assert wait_i < wait_b, \
            f"incremental wait {wait_i:.4f}s >= batch wait {wait_b:.4f}s"
        assert wait_i < cfg.batch_window, \
            f"incremental wait {wait_i:.4f}s above the " \
            f"{cfg.batch_window}s batch-window floor"
        assert wf_i >= 0.90 * wf_b, \
            f"incremental welfare/req {wf_i:.4f} < 90% of batch {wf_b:.4f}"


def run_federation_cell(family: str, n_agents: int, n_dialogues: int,
                        super_hubs: int, *, seed: int = 0,
                        parallel: str = "inline",
                        epoch: float | None = None) -> dict:
    """One federation cell at the `SCALE_1K` preset knobs.

    The admission window scales with the fleet (SCALE_1K's 2 dialogues
    per agent); ``super_hubs=1`` is the bit-exact single-heap oracle
    (same `EventSimulator` semantics), which is how the comparison rows
    are produced.  Audit ledgers stay on: the exactly-once gates replay
    every shard's hash chain.
    """
    cfg = SCALE_1K
    spec = WorkloadSpec(family, n_dialogues=n_dialogues, seed=seed + 1)
    fed = build_federation(
        iter_dialogues(spec), n_agents=n_agents,
        super_hubs=super_hubs,
        arrivals=PoissonArrivals(rate=cfg.arrival_rate(n_agents),
                                 seed=seed + 2),
        seed=seed, engine_mode=cfg.engine_mode,
        agents_per_hub=cfg.agents_per_hub,
        max_inflight=max(64, cfg.max_inflight * n_agents // cfg.n_agents),
        router_kwargs=dict(solver=cfg.solver, warm_start=cfg.warm_start,
                           audit_ledger=True),
        loop_kwargs=dict(batch_cap=cfg.batch_cap,
                         batch_window=cfg.batch_window,
                         max_new_tokens=cfg.max_new_tokens, lean=True,
                         max_events=20_000_000, max_rounds=2_000_000),
        cluster_kwargs=dict(max_new_tokens=cfg.max_new_tokens),
        epoch=epoch if epoch is not None else cfg.epoch, parallel=parallel)
    t0 = time.perf_counter()
    out = fed.run()
    out["bench_wall_s"] = time.perf_counter() - t0
    return out


def _fed_row(family: str, n_agents: int, n_dialogues: int, s: int,
             out: dict) -> None:
    """Emit one federation CSV row (routing + boundary-phase attribution,
    spill/gossip health, exactly-once verdict)."""
    rep = out["routing"]
    fed = out["federation"]
    eo = fed["exactly_once"]
    wf = out["accounts"]["welfare_realized"] / max(out.get("n", 1), 1)
    cols = [
        f"route_us_per_req={_us_per_req(out):.1f}",
        f"gossip_ms={1e3 * _wall(rep, 'federation_gossip'):.2f}",
        f"fed_spill_ms={1e3 * _wall(rep, 'federation_spill'):.2f}",
        f"migrate_ms={1e3 * _wall(rep, 'federation_migrate'):.2f}",
        f"routing_s={rep['routing_wall_s']:.2f}",
        f"epochs={out['epochs']}",
        f"spilled={fed['spill_migrated']}/{fed['spill_candidates']}",
        f"stale_max={fed['gossip']['max_staleness_epochs']}",
        f"welfare_per_req={wf:.4f}",
        f"n={out.get('n', 0)}",
        f"wait_ms={1e3 * out.get('queue_wait_mean_s', 0.0):.1f}",
        f"done={out.get('dialogues_completed', 0)}"
        f"/{out.get('dialogues_arrived', 0)}",
        f"eo={eo['ok']}",
        f"truncated={out.get('truncated', False)}",
    ]
    emit(f"servingscale/fed_{family}_a{n_agents}_d{n_dialogues}_s{s}",
         out["bench_wall_s"] * 1e6, " ".join(cols))


def _gate_federation(out: dict, n_dialogues: int, super_hubs: int) -> None:
    """Structural federation gates: exactly-once settlement verified by
    ledger replay, nothing lost or double-settled, migrations balanced,
    spill never consumed a digest staler than one epoch, and the epoch
    boundaries' own cost stayed inside the routing wall-clock bound."""
    eo = out["federation"]["exactly_once"]
    assert eo["ok"], f"exactly-once audit failed: {eo}"
    assert eo["ledger_replay_ok"] and eo["ledgers_attached"] == super_hubs
    assert eo["lost_dialogues"] == 0 and eo["dialogues_conserved"]
    assert eo["migrations_balanced"]
    assert out["dialogues_completed"] + out["unfinished_dialogues"] \
        == n_dialogues
    assert not out["truncated"], "federation cell truncated"
    assert out["federation"]["gossip"]["max_staleness_epochs"] <= 1
    us = _us_per_req(out)
    assert 0 < us < FED_ROUTE_US_BOUND, \
        f"routing+boundary wall-clock {us:.1f} us per request out of the " \
        f"(0, {FED_ROUTE_US_BOUND}) regression bound"


def run_federation(smoke: bool = False):
    """The hubs-of-hubs study: federated vs single-heap serving.

    Smoke: one reduced cell, S=1 vs S=4, with the exactly-once /
    staleness / welfare-retention gates.  Full: the FED_SIZES grid —
    a 256-agent comparison pair plus the SCALE_1K headline row (1024
    agents / 100k dialogues / 8 process-parallel shards), gated on
    exactly-once settlement and completion but not compared against a
    single heap (sustaining that cell on one heap is the problem
    federation exists to solve).
    """
    family = WORKLOADS[0]
    sizes = FED_SMOKE if (smoke or QUICK) else FED_SIZES
    for i, (n_agents, n_dialogues, s) in enumerate(sizes):
        headline = not smoke and i == len(sizes) - 1
        fed = run_federation_cell(
            family, n_agents, n_dialogues, s,
            parallel="process" if headline else "inline")
        _fed_row(family, n_agents, n_dialogues, s, fed)
        _gate_federation(fed, n_dialogues, s)
        if headline:
            continue   # no single-heap twin at 1k agents (see docstring)
        single = run_federation_cell(family, n_agents, n_dialogues, 1)
        _fed_row(family, n_agents, n_dialogues, 1, single)
        wf_s = single["accounts"]["welfare_realized"] / max(single["n"], 1)
        wf_f = fed["accounts"]["welfare_realized"] / max(fed["n"], 1)
        emit(f"servingscale/fed_{family}_a{n_agents}_welfare_retention",
             fed["bench_wall_s"] * 1e6,
             f"single={wf_s:.4f} federated={wf_f:.4f} "
             f"ratio={wf_f / wf_s if wf_s else 0.0:.3f}")
        # partitioned markets + spill penalties cost a bounded welfare
        # slice vs the global auction; 0.75 catches a structural break
        # (e.g. spill routing everything through the penalty) while
        # leaving room for partition noise at small fleets
        assert wf_f >= 0.75 * wf_s, \
            f"federated welfare/req {wf_f:.4f} < 75% of single-heap {wf_s:.4f}"


def run(smoke: bool = False, oracle: bool = False):
    """Sweep the (family x fleet-size) grid."""
    quick = smoke or QUICK
    sizes = SMOKE_SIZES if quick else SIZES
    families = WORKLOADS[:1] if smoke else WORKLOADS
    for family in families:
        for n_agents, n_dialogues in sizes:
            out = run_cell(family, n_agents, n_dialogues)
            route_ms = _row(family, n_agents, n_dialogues, out)
            if smoke:
                # structural gates (size-independent correctness)
                rep = out["routing"]
                assert out["dialogues_completed"] == n_dialogues, \
                    f"{out['dialogues_completed']}/{n_dialogues} completed"
                assert not out["truncated"], "smoke run truncated"
                # regression bound on the routing wall-clock per router
                # invocation (ROUTE_MS_BOUND: an order of magnitude of
                # headroom over the measured smoke cell)
                assert 0 < route_ms < ROUTE_MS_BOUND, \
                    f"routing {route_ms:.3f} ms per batch out of the " \
                    f"(0, {ROUTE_MS_BOUND}) regression bound"
                # the event loop never invokes the router without work
                assert rep["empty_route_calls"] == 0
                assert rep["route_requests"] >= out["dispatched_requests"]
                for need in ("route_batch", "phase1_predict",
                             "phase2_solve[dense]", "phase4_feedback"):
                    assert need in rep["phases"], f"missing phase {need}"
                assert out["requests_per_dialogue_max"] >= 1
            else:
                assert not out["truncated"], \
                    f"{family} a{n_agents} d{n_dialogues} truncated"
        # incremental-vs-batch arrival latency at the smallest cell (gated
        # in smoke; the full sweep repeats it at the second size too)
        n_a, n_d = sizes[0]
        _incremental_study(family, n_a, n_d, gate=True)
        if not quick and len(sizes) > 1:
            _incremental_study(family, sizes[1][0], sizes[1][1], gate=False)
        if oracle and not smoke:
            # exact-solver comparison row: the Python oracle at micro-batch
            # markets (its blowup is market-size-driven — mcmf_scaling.py)
            n_agents, n_dialogues = sizes[0]
            out = run_cell(family, n_agents, max(200, n_dialogues // 5),
                           solver="mcmf")
            _row(f"{family}_mcmf", n_agents, max(200, n_dialogues // 5), out)


def main():
    """CLI entry point."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="one reduced cell + structural gates (CI)")
    ap.add_argument("--oracle", action="store_true",
                    help="add an exact-MCMF comparison row per family")
    ap.add_argument("--federation", action="store_true",
                    help="run the hubs-of-hubs study (federated vs "
                         "single-heap; SCALE_1K headline row) instead of "
                         "the single-heap sweep")
    args = ap.parse_args()
    if args.federation:
        run_federation(smoke=args.smoke)
    else:
        run(smoke=args.smoke, oracle=args.oracle)


if __name__ == "__main__":
    start()
    main()
