"""Fused device-resident routing step (`core/routing_fused`) vs the staged
oracle: decision parity, welfare optimality, construction guards, retrace
bounds.

The fused program runs float32 on device while the staged Phase 1 is
float64 NumPy, so parity tests use HETEROGENEOUS agents (distinct per-agent
token prices) — under exact column ties the two precisions can break a tie
into different equally-optimal permutations (same welfare, same payments),
which is degeneracy, not divergence.  With a unique optimum the contract is
strict: identical assignments, payments and QoS estimates within float32
tolerance, on every batch of a lockstep run with synchronized Phase-4
feedback."""
import numpy as np
import pytest

from repro.core.mechanism import (AgentInfo, CompletionObs, IEMASRouter,
                                  Request)
from repro.core.pricing import TokenPrices
from repro.core.routing_fused import FUSED_SOLVERS

PAY_TOL = 1e-5          # float32 welfare -> float64 Clarke pivot drift
EST_TOL = 1e-4          # QoS estimate drift (relative scale ~1)


def hetero_agents(m: int = 5, cap: int = 2) -> list[AgentInfo]:
    """Distinct per-agent prices => unique welfare optimum (no ties)."""
    out = []
    for i in range(m):
        pr = TokenPrices(0.01 * (1 + i / m), 0.001 * (1 + i / m),
                         0.03 * (1 + i / m))
        out.append(AgentInfo(f"a{i}", pr, cap,
                             ("dialogue",) if i % 2 == 0
                             else ("dialogue", "reasoning"),
                             scale=4.0 + i, recurrent=(i == 3),
                             cache_slots=2 if i == 1 else 0))
    return out


def make_batch(n: int, t: int, seed: int, parents: bool = False):
    rng = np.random.default_rng(seed * 1000 + t)
    reqs = []
    for j in range(n):
        meta = {}
        if parents and j % 3 == 1:
            meta["parent_sessions"] = (f"d{(j + 1) % 4}", f"d{(j + 2) % 4}")
        reqs.append(Request(f"r{t}_{j}", f"d{j % 4}",
                            rng.integers(0, 50, int(rng.integers(5, 30))),
                            turn=t, domain="dialogue" if j % 2 == 0
                            else "reasoning", meta=meta))
    return reqs


TELEMETRY = {"router_inflight": 2, "router_rps": 1.0,
             "agent_inflight": {"a0": 1}, "agent_rps": {"a1": 0.5}}


def clone(reqs):
    return [Request(r.request_id, r.dialogue_id, r.tokens.copy(), r.turn,
                    r.domain, meta=dict(r.meta)) for r in reqs]


def lockstep(ref, fused, n_batches: int, seed: int, parents: bool = False,
             rng=None):
    """Route identical batches through both routers with synchronized
    feedback; yields (batch index, ref decisions, fused decisions)."""
    rng = rng or np.random.default_rng(seed + 99)
    for t in range(n_batches):
        reqs = make_batch(int(rng.integers(2, 9)), t, seed, parents=parents)
        dr = ref.route_batch(reqs, dict(TELEMETRY))
        df = fused.route_batch(clone(reqs), dict(TELEMETRY))
        yield t, dr, df
        for d in dr:            # identical Phase-4 observations to both
            if d.agent_id:
                obs = CompletionObs(latency=0.03 + 0.01 * rng.random(),
                                    n_prompt=len(d.request.tokens), n_hit=0,
                                    n_gen=20, quality=0.7)
                ref.on_complete(d.request.request_id, obs)
                fused.on_complete(d.request.request_id, obs)


def assert_decisions_match(t, dr, df):
    """Two-tier parity gate.

    Tier 1 (the common case): identical assignments => payments and QoS
    estimates must agree to float32 tolerance.  Tier 2: when the float32
    welfare bits flip the ε-scaling auction onto a DIFFERENT assignment,
    that assignment must be welfare-equivalent — total welfare within the
    auction's own ε-optimality gap (measured ~1e-6 relative on the seeds
    that hit this; payments then differ because Clarke pivots price two
    different equilibria, which is tie degeneracy, not an error)."""
    a_r = [d.agent_id for d in dr]
    a_f = [d.agent_id for d in df]
    w_r = sum(d.welfare_weight for d in dr)
    w_f = sum(d.welfare_weight for d in df)
    if a_f != a_r:
        assert abs(w_f - w_r) <= 1e-5 * max(1.0, abs(w_r)), \
            f"batch {t}: fused {a_f} (welfare {w_f}) != staged {a_r} " \
            f"(welfare {w_r}) beyond the ε-optimality gap"
        return False
    for r, f in zip(dr, df):
        assert abs(r.payment - f.payment) < PAY_TOL, \
            f"batch {t}: payment {f.payment} vs {r.payment}"
        if r.agent_id:
            assert abs(r.estimate.latency - f.estimate.latency) < EST_TOL
            assert abs(r.estimate.cost - f.estimate.cost) < EST_TOL
            assert abs(r.estimate.quality - f.estimate.quality) < EST_TOL
    return True


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("warm", [False, True])
def test_fused_matches_staged_dense_jax(seed, warm):
    """Full decision parity vs the staged dense-jax path over randomized
    lockstep batches (cold and warm-started)."""
    kw = dict(solver="dense-jax", n_hubs=1, warm_start=warm)
    ref = IEMASRouter(hetero_agents(), **kw)
    fused = IEMASRouter(hetero_agents(), fused=True, **kw)
    for t, dr, df in lockstep(ref, fused, 5, seed):
        if not assert_decisions_match(t, dr, df):
            break   # post-divergence feedback lands on different agents


def test_fused_matches_staged_with_parent_credit():
    """DAG parent-session credit (scatter-max inside the program) keeps
    parity with the staged `parent_credit` host path."""
    kw = dict(solver="dense-jax", n_hubs=1, warm_start=True)
    ref = IEMASRouter(hetero_agents(), **kw)
    fused = IEMASRouter(hetero_agents(), fused=True, **kw)
    for t, dr, df in lockstep(ref, fused, 5, seed=7, parents=True):
        if not assert_decisions_match(t, dr, df):
            break


def test_fused_matches_staged_pallas():
    """The pallas bid-round variant composes into the fused program with
    the same decision parity (interpret mode off-TPU: slow, fewer rounds)."""
    kw = dict(solver="pallas", n_hubs=1, warm_start=False)
    ref = IEMASRouter(hetero_agents(m=4), **kw)
    fused = IEMASRouter(hetero_agents(m=4), fused=True, **kw)
    for t, dr, df in lockstep(ref, fused, 2, seed=3):
        if not assert_decisions_match(t, dr, df):
            break


@pytest.mark.parametrize("ref_solver", ["mcmf", "dense"])
def test_fused_welfare_within_gap_of_reference(ref_solver):
    """Backends that cannot compose into the program (exact MCMF, the
    host-vectorized dense auction) are covered by the ε-scaling optimality
    gap: the fused assignment's total welfare matches the reference
    backend's to within n·ε_final (tiny vs the welfare scale)."""
    kw = dict(n_hubs=1, warm_start=False)
    ref = IEMASRouter(hetero_agents(), solver=ref_solver, **kw)
    fused = IEMASRouter(hetero_agents(), solver="dense-jax", fused=True, **kw)
    for t, dr, df in lockstep(ref, fused, 4, seed=5):
        w_r = sum(d.welfare_weight for d in dr)
        w_f = sum(d.welfare_weight for d in df)
        assert abs(w_f - w_r) <= 1e-3 * max(1.0, w_r), \
            f"batch {t}: fused welfare {w_f} vs {ref_solver} {w_r}"
        if [d.agent_id for d in dr] != [d.agent_id for d in df]:
            break   # states drift once feedback lands on different agents


def test_fused_init_requires_single_hub():
    with pytest.raises(ValueError, match="n_hubs=1"):
        IEMASRouter(hetero_agents(), solver="dense-jax", n_hubs=2,
                    fused=True)


@pytest.mark.parametrize("solver", ["mcmf", "dense"])
def test_fused_init_requires_staged_solver(solver):
    assert solver not in FUSED_SOLVERS
    with pytest.raises(ValueError):
        IEMASRouter(hetero_agents(), solver=solver, n_hubs=1, fused=True)


def test_fused_shape_buckets_bound_retracing():
    """Satellite of the perf contract: every batch size inside one pow-2
    bucket reuses the same traced program (mirrors the `descend_jax`
    retrace test), even with Phase-4 feedback growing the forests between
    batches.  Serving-scale smoke shapes: fleet 16, batches 9..16."""
    router = IEMASRouter(hetero_agents(m=16, cap=2), solver="dense-jax",
                         n_hubs=1, warm_start=False, fused=True)
    rng = np.random.default_rng(11)

    def route(n, t):
        reqs = make_batch(n, t, seed=13)
        for d in router.route_batch(reqs, dict(TELEMETRY)):
            if d.agent_id:
                router.on_complete(
                    d.request.request_id,
                    CompletionObs(latency=0.02 + 0.01 * rng.random(),
                                  n_prompt=len(d.request.tokens), n_hit=0,
                                  n_gen=16, quality=0.75))

    route(12, 0)                       # trace the (nb=16, mb=16) bucket
    before = router._fused.cache_size()
    for t, n in enumerate(range(9, 17)):
        route(n, t + 1)
    grew = router._fused.cache_size() - before
    # headroom 2: a forest split can cross the node-pool pow-2 bucket and
    # the ledger arena can regrow once as sessions accumulate
    assert grew <= 2, f"fused step retraced {grew} times within one bucket"


def test_fused_profiler_counters():
    """Each step is one assemble -> device -> settle sequence of spans on
    the attached profiler: one device span (the batch's one device->host
    boundary) per route call."""
    from repro.serving.simulator import RoutingProfiler

    router = IEMASRouter(hetero_agents(), solver="dense-jax", n_hubs=1,
                         fused=True)
    router.profiler = prof = RoutingProfiler()
    for t in range(3):
        router.route_batch(make_batch(4, t, seed=17), dict(TELEMETRY))
    rep = prof.report()
    for part in ("fused.assemble", "fused.device", "fused.settle"):
        assert rep["phases"][part]["calls"] == 3 == \
            rep["phases"]["fused_route"]["calls"], part
    assert rep["counters"]["fused.device.rounds"] > 0
    assert prof.fused_retraces >= 1      # first call traced something
