"""Event-driven serving simulator: determinism, closed-loop parity with
run_workload, streaming admission, profiler attribution, 10k smoke.

Everything here runs on ``engine_mode="analytic"`` clusters — deterministic
virtual service times, so records can be compared bit-for-bit."""
import json

import numpy as np
import pytest

from repro.core import IEMASRouter
from repro.serving import (DialogueScript, EventSimulator, PoissonArrivals,
                           RoutingProfiler, SimCluster, SyncArrivals,
                           TraceArrivals, WorkloadSpec, generate,
                           iter_dialogues, load_trace, make_arrivals,
                           run_workload)


def _fresh(seed=0, n_agents=4, fail=0.0, **router_kw):
    cluster = SimCluster(n_agents=n_agents, seed=seed, max_new_tokens=3,
                         engine_mode="analytic", fail_prob=fail)
    kw = dict(solver="dense", n_hubs=2, warm_start=True)
    kw.update(router_kw)
    router = IEMASRouter(cluster.agent_infos(), **kw)
    return cluster, router


def _sig(cluster):
    """Bit-comparable per-record signature, in completion order."""
    return [(r.request.request_id, r.request.dialogue_id, r.request.turn,
             r.agent_id, r.n_prompt, r.n_hit, r.payment, r.latency,
             r.dispatched_at) for r in cluster.records]


# -------------------------------------------------- closed-loop parity --
@pytest.mark.parametrize("fail", [0.0, 0.2])
def test_lockstep_parity_with_run_workload(fail):
    """With synchronous arrivals and quantized round ticks the event
    simulator reproduces run_workload's decisions bit-for-bit — including
    the fault path (same rng draw order)."""
    dlg = generate(WorkloadSpec("coqa_like", n_dialogues=7, seed=3))
    c1, r1 = _fresh(fail=fail)
    m1 = run_workload(c1, r1, dlg, max_rounds=3000, max_new_tokens=3,
                      batch_per_round=4)
    c2, r2 = _fresh(fail=fail)
    m2 = EventSimulator(c2, r2, dlg, arrivals=SyncArrivals(), batch_cap=4,
                        quantize=0.05, max_rounds=3000,
                        max_new_tokens=3).run()
    assert _sig(c1) == _sig(c2)
    for key in ("n", "kv_hit_rate", "latency_ms_mean", "cost_mean",
                "quality_mean", "completed_turns", "dispatched_requests"):
        assert m1[key] == m2[key], key
    assert m2["dialogues_completed"] == len(dlg)
    assert not m1["truncated"] and not m2["truncated"]


def test_lockstep_parity_other_workloads():
    """Parity holds across workload families (different turn structure)."""
    for family in ("quac_like", "hotpot_like"):
        dlg = generate(WorkloadSpec(family, n_dialogues=4, seed=1))
        c1, r1 = _fresh(seed=2)
        run_workload(c1, r1, dlg, max_rounds=2000, max_new_tokens=3)
        c2, r2 = _fresh(seed=2)
        EventSimulator(c2, r2, dlg, arrivals=SyncArrivals(), batch_cap=16,
                       quantize=0.05, max_rounds=2000,
                       max_new_tokens=3).run()
        assert _sig(c1) == _sig(c2), family


# ------------------------------------------------------- determinism --
def test_event_ordering_determinism():
    """Two identical open-loop runs (Poisson arrivals, failures on) replay
    the exact same event order, decisions and metrics under a fixed seed."""
    def once():
        cluster, router = _fresh(seed=5, fail=0.15)
        spec = WorkloadSpec("coqa_like", n_dialogues=12, seed=9)
        out = EventSimulator(
            cluster, router, iter_dialogues(spec),
            arrivals=PoissonArrivals(rate=6.0, seed=11), batch_cap=8,
            batch_window=0.02, max_inflight=6, max_new_tokens=3).run()
        return _sig(cluster), out

    sig_a, out_a = once()
    sig_b, out_b = once()
    assert sig_a == sig_b
    drop = ("wall_time_s",)  # the only wall-clock-dependent key
    assert {k: v for k, v in out_a.items() if k not in drop} == \
        {k: v for k, v in out_b.items() if k not in drop}


# ------------------------------------------------ streaming admission --
def test_admission_window_bounds_inflight():
    """10k-style streaming: at most max_inflight dialogues hold state at
    once; the rest queue in the backlog and everything still completes."""
    cluster, router = _fresh(seed=1)
    spec = WorkloadSpec("coqa_like", n_dialogues=10, seed=4)
    out = EventSimulator(cluster, router, iter_dialogues(spec),
                         arrivals=SyncArrivals(), batch_cap=8,
                         batch_window=0.02, max_inflight=3,
                         max_new_tokens=3).run()
    assert out["peak_inflight"] <= 3
    assert out["dialogues_arrived"] == 10
    assert out["dialogues_completed"] == 10
    assert out["unfinished_dialogues"] == 0 and not out["truncated"]
    # a window that can never admit anything is a configuration error, not
    # a silent no-op run
    with pytest.raises(ValueError, match="max_inflight"):
        EventSimulator(cluster, router, [], max_inflight=0)


def test_trace_arrivals_and_open_loop_pacing():
    """TraceArrivals replays explicit timestamps; arrivals pace admission
    (the second dialogue cannot be dispatched before its arrival time)."""
    cluster, router = _fresh(seed=3)
    dlg = generate(WorkloadSpec("hotpot_like", n_dialogues=3, seed=2))
    out = EventSimulator(cluster, router, dlg,
                         arrivals=TraceArrivals((0.0, 2.0, 2.5)),
                         batch_cap=4, batch_window=0.01,
                         max_new_tokens=3).run()
    assert out["dialogues_completed"] == 3
    first_dispatch = {}
    for rec in cluster.records:
        did = rec.request.dialogue_id
        first_dispatch.setdefault(did, rec.dispatched_at)
    times = [first_dispatch[d.dialogue_id] for d in dlg]
    assert times[1] >= 2.0 and times[2] >= 2.5


def test_short_trace_ends_arrivals_loudly():
    """A trace shorter than the dialogue stream stops arrivals (zip
    semantics) but flags the run instead of crashing or dropping silently."""
    cluster, router = _fresh(seed=3)
    dlg = generate(WorkloadSpec("hotpot_like", n_dialogues=5, seed=2))
    with pytest.warns(RuntimeWarning, match="arrival process exhausted"):
        out = EventSimulator(cluster, router, dlg,
                             arrivals=TraceArrivals((0.0, 0.5)),
                             batch_cap=4, batch_window=0.01,
                             max_new_tokens=3).run()
    assert out["truncated"]
    assert out["dialogues_arrived"] == 2
    assert out["dialogues_completed"] == 2


def test_truncation_reported_with_warning():
    """Hitting the round budget surfaces unfinished dialogues + a warning
    instead of returning partial metrics silently."""
    cluster, router = _fresh(seed=0)
    dlg = generate(WorkloadSpec("coqa_like", n_dialogues=6, seed=3))
    with pytest.warns(RuntimeWarning, match="truncated"):
        out = EventSimulator(cluster, router, dlg, arrivals=SyncArrivals(),
                             batch_cap=2, quantize=0.05, max_rounds=3,
                             max_new_tokens=3).run()
    assert out["truncated"]
    assert out["unfinished_dialogues"] > 0
    assert out["dialogues_completed"] < 6


# ------------------------------------------------------- profiler --
def test_profiler_attribution():
    """The RoutingProfiler sees every phase the router runs and reports
    absolute routing wall-clock, per-phase calls and span counters."""
    cluster, router = _fresh(seed=2)
    prof = RoutingProfiler()
    spec = WorkloadSpec("coqa_like", n_dialogues=6, seed=7)
    out = EventSimulator(cluster, router, iter_dialogues(spec),
                         arrivals=PoissonArrivals(rate=8.0, seed=3),
                         batch_cap=8, profiler=prof, lean=True,
                         max_new_tokens=3).run()
    rep = out["routing"]
    phases = rep["phases"]
    assert rep["routing_wall_s"] > 0
    assert rep["routing_wall_s"] == pytest.approx(
        phases["route_batch"]["wall_s"] + phases["phase4_feedback"]["wall_s"])
    for phase in ("route_batch", "phase1_predict", "phase2_solve[dense]",
                  "price_book", "phase4_feedback"):
        assert phase in phases, phase
        assert phases[phase]["calls"] > 0
    # nested phases are inside the umbrella, never bigger than it
    assert phases["phase1_predict"]["wall_s"] <= \
        phases["route_batch"]["wall_s"]
    # one feedback span per completion, carrying the records' own counts
    assert phases["phase4_feedback"]["calls"] == len(cluster.records)
    assert rep["counters"]["phase4_feedback.n_hit"] == sum(
        r.n_hit for r in cluster.records)
    assert rep["counters"]["phase4_feedback.n_prompt"] == sum(
        r.n_prompt for r in cluster.records)


def test_profiler_noop_when_absent():
    """Without a profiler nothing is attached and routing still works."""
    cluster, router = _fresh(seed=2)
    assert cluster.profiler is None and router.profiler is None
    out = EventSimulator(cluster, router,
                         generate(WorkloadSpec("coqa_like", n_dialogues=2,
                                               seed=1)),
                         max_new_tokens=3).run()
    assert "routing" not in out
    assert out["dialogues_completed"] == 2


# ---------------------------------------------- empty-round guard --
def test_no_empty_route_rounds_in_quantize_mode():
    """ISSUE-6 satellite 3 regression (fails pre-fix): the quantize regime
    fires a ROUTE tick on every round boundary even while all dialogues are
    busy; ticks with no ready work must not invoke the router, count a
    round, burn max_rounds budget, or fire on_round."""
    cluster, router = _fresh(seed=2)
    prof = RoutingProfiler()
    dlg = generate(WorkloadSpec("coqa_like", n_dialogues=5, seed=3))
    on_round_calls = []
    out = EventSimulator(cluster, router, dlg, arrivals=SyncArrivals(),
                         batch_cap=4, quantize=0.05, profiler=prof,
                         max_new_tokens=3,
                         on_round=lambda r, c: on_round_calls.append(r)).run()
    assert out["dialogues_completed"] == 5 and not out["truncated"]
    # every counted round was one real router invocation with work in it
    assert out["rounds"] == prof.calls["route_batch"]
    assert prof.empty_route_calls == 0
    assert prof.route_requests >= out["dispatched_requests"]
    assert on_round_calls == list(range(1, out["rounds"] + 1))


def test_empty_round_guard_preserves_decisions():
    """The guard is pure accounting: the routed records are bit-identical
    to the run_workload oracle (the lockstep parity contract still holds
    with rounds now counting only real router invocations)."""
    dlg = generate(WorkloadSpec("quac_like", n_dialogues=5, seed=8))
    c1, r1 = _fresh(seed=6)
    run_workload(c1, r1, dlg, max_rounds=2000, max_new_tokens=3,
                 batch_per_round=3)
    c2, r2 = _fresh(seed=6)
    out = EventSimulator(c2, r2, dlg, arrivals=SyncArrivals(), batch_cap=3,
                         quantize=0.05, max_rounds=2000,
                         max_new_tokens=3,
                         profiler=RoutingProfiler()).run()
    assert _sig(c1) == _sig(c2)
    assert out["routing"]["empty_route_calls"] == 0


# ------------------------------------------------- incremental mode --
def test_incremental_mode_dispatches_and_reconciles():
    """incremental=True: once standing duals exist, newly-ready dialogues
    are provisionally dispatched at posted prices (no batch-window wait);
    the next batch auction or the completion path retires every
    provisional, and the run drains cleanly."""
    cluster, router = _fresh(seed=4)
    spec = WorkloadSpec("coqa_like", n_dialogues=10, seed=6)
    out = EventSimulator(cluster, router, iter_dialogues(spec),
                         arrivals=PoissonArrivals(rate=4.0, seed=7),
                         batch_cap=8, batch_window=0.05, incremental=True,
                         max_new_tokens=3).run()
    assert out["dialogues_completed"] == 10 and not out["truncated"]
    acc = router.accounts
    assert out["incremental_dispatched"] == acc["incremental_routed"]
    assert acc["incremental_routed"] > 0
    assert acc["incremental_confirmed"] + acc["incremental_rerouted"] <= \
        acc["incremental_routed"]
    # nothing left provisional after the run drains
    assert not router._provisional and not router._prov_units


def test_incremental_mode_deterministic():
    """Two identical incremental runs replay the same records + metrics."""
    def once():
        cluster, router = _fresh(seed=9)
        spec = WorkloadSpec("coqa_like", n_dialogues=8, seed=5)
        out = EventSimulator(cluster, router, iter_dialogues(spec),
                             arrivals=PoissonArrivals(rate=5.0, seed=13),
                             batch_cap=6, batch_window=0.03,
                             incremental=True, max_new_tokens=3).run()
        return _sig(cluster), out
    sig_a, out_a = once()
    sig_b, out_b = once()
    assert sig_a == sig_b
    drop = ("wall_time_s",)
    assert {k: v for k, v in out_a.items() if k not in drop} == \
        {k: v for k, v in out_b.items() if k not in drop}


def test_incremental_off_is_default_noop():
    """The flag defaults off; without it nothing is provisionally routed."""
    cluster, router = _fresh(seed=1)
    dlg = generate(WorkloadSpec("coqa_like", n_dialogues=4, seed=2))
    out = EventSimulator(cluster, router, dlg, arrivals=SyncArrivals(),
                         batch_cap=8, quantize=0.05, max_new_tokens=3).run()
    assert out["incremental_dispatched"] == 0
    assert router.accounts["incremental_routed"] == 0


# ----------------------------------------- id/wait-clock regressions --
def test_request_ids_unique_across_deferral_and_faults():
    """ISSUE-7 satellite 1 regression (fails pre-fix): incremental offers
    that get deferred must still burn their request id — under a mixed
    deferral/fault trace no id may ever be re-issued to a different
    request (router/profiler state is keyed by request_id)."""
    cluster, router = _fresh(seed=4, fail=0.15)
    seen_rids, deferred = [], [0]
    orig_batch, orig_inc = router.route_batch, router.route_incremental

    def batch(reqs, telem, free_slots=None):
        seen_rids.extend(r.request_id for r in reqs)
        return orig_batch(reqs, telem, free_slots=free_slots)

    def inc(reqs, telem, free_slots=None):
        seen_rids.extend(r.request_id for r in reqs)
        decs = orig_inc(reqs, telem, free_slots=free_slots)
        deferred[0] += sum(d.agent_id is None for d in decs)
        return decs

    router.route_batch, router.route_incremental = batch, inc
    spec = WorkloadSpec("coqa_like", n_dialogues=10, seed=6)
    out = EventSimulator(cluster, router, iter_dialogues(spec),
                         arrivals=PoissonArrivals(rate=6.0, seed=7),
                         batch_cap=6, batch_window=0.03, incremental=True,
                         max_new_tokens=3).run()
    assert out["dialogues_completed"] == 10 and not out["truncated"]
    # the trace really mixed the regimes: provisional dispatches AND
    # deferred offers (the pre-fix id-reuse trigger) both happened
    assert out["incremental_dispatched"] > 0
    assert deferred[0] > 0
    assert len(seen_rids) == len(set(seen_rids)), \
        "a request_id was re-issued to a different request"


def test_fault_retry_preserves_wait_clock():
    """ISSUE-7 satellite 2 regression (fails pre-fix): a failed dispatch
    re-queues its turn with the ORIGINAL ready time — resetting the clock
    to the failure completion under-reports queueing wait across retries."""
    cluster = SimCluster(n_agents=1, seed=0, max_new_tokens=3,
                         engine_mode="analytic", quarantine_cooldown=0.5)
    router = IEMASRouter(cluster.agent_infos(), solver="dense", n_hubs=1,
                         warm_start=True)
    rt = next(iter(cluster.agents.values()))
    rt.down_until = 1.0   # first dispatch fails; the agent recovers at t=1
    rng = np.random.default_rng(0)
    dlg = [DialogueScript("w0", next(iter(rt.info.domains)),
                          [rng.integers(1, 255, 20, dtype=np.int32)], 0.3)]
    out = EventSimulator(cluster, router, dlg, arrivals=SyncArrivals(),
                         batch_cap=2, quantize=0.05, max_new_tokens=3).run()
    assert out["dialogues_completed"] == 1 and not out["truncated"]
    [rec] = cluster.records
    t_disp = rec.dispatched_at
    assert t_disp >= 1.0 - 1e-9   # redispatch only after the recovery
    # two dispatches accrued wait: the failed one waited 0 (ready and
    # dispatched at t=0), the retry is charged from the original t=0 ready
    # time -> mean wait is t_disp/2 exactly (pre-fix: (t_disp - 0.05)/2,
    # the clock restarted at the failure completion)
    assert out["queue_wait_mean_s"] == pytest.approx(t_disp / 2)


# ------------------------------------------------- trace CLI wiring --
def test_load_trace_and_make_arrivals(tmp_path):
    """ISSUE-7 satellite 3: load_trace parses timestamp files (comments,
    blanks, loud errors) and make_arrivals wires every process by name."""
    p = tmp_path / "trace.txt"
    p.write_text("# arrival trace\n0.0\n1.5  # second dialogue\n\n2.5\n")
    ts = load_trace(p)
    assert ts == (0.0, 1.5, 2.5)
    arr = make_arrivals("trace", trace=ts)
    assert isinstance(arr, TraceArrivals)
    assert list(arr.times()) == [0.0, 1.5, 2.5]
    assert isinstance(make_arrivals("sync"), SyncArrivals)
    assert isinstance(make_arrivals("poisson", rate=2.0), PoissonArrivals)
    with pytest.raises(ValueError, match="--trace-file"):
        make_arrivals("trace")          # no timestamps supplied
    with pytest.raises(KeyError, match=r"sync\|poisson\|trace"):
        make_arrivals("uniform")
    bad = tmp_path / "bad.txt"
    bad.write_text("0.0\nnot-a-time\n")
    with pytest.raises(ValueError, match=r"bad\.txt:2"):
        load_trace(bad)
    empty = tmp_path / "empty.txt"
    empty.write_text("# nothing here\n\n")
    with pytest.raises(ValueError, match="empty arrival trace"):
        load_trace(empty)


def test_trace_sorted_validation_error_path():
    """An out-of-order trace fails loudly — directly and through a run."""
    with pytest.raises(ValueError, match="non-decreasing"):
        list(TraceArrivals((0.0, 2.0, 1.0)).times())
    cluster, router = _fresh(seed=1)
    dlg = generate(WorkloadSpec("hotpot_like", n_dialogues=3, seed=2))
    with pytest.raises(ValueError, match="non-decreasing"):
        EventSimulator(cluster, router, dlg,
                       arrivals=TraceArrivals((0.0, 2.0, 1.0)),
                       batch_cap=4, batch_window=0.01,
                       max_new_tokens=3).run()


def test_serve_cli_trace_file(tmp_path, capsys, monkeypatch):
    """--trace-file reaches the event simulator end to end (the arrivals
    pace admission), and DAG workloads are rejected in closed mode."""
    from repro.launch import serve
    # the CLI's persistent compile cache stays off under test
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    trace = tmp_path / "arrivals.txt"
    trace.write_text("0.0\n0.4\n")
    monkeypatch.setattr("sys.argv", [
        "serve", "--sim-mode", "event", "--trace-file", str(trace),
        "--workload", "hotpot_like", "--agents", "4", "--dialogues", "2",
        "--solver", "dense", "--router", "iemas"])
    serve.main()
    out = json.loads(capsys.readouterr().out)
    assert out["dialogues_arrived"] == 2
    assert out["dialogues_completed"] == 2 and not out["truncated"]
    # second dialogue cannot dispatch before its traced arrival at t=0.4
    assert out["sim_time_s"] >= 0.4
    monkeypatch.setattr("sys.argv", ["serve", "--workload", "dag_handoff"])
    with pytest.raises(SystemExit):
        serve.main()                     # DAG needs --sim-mode event


# ------------------------------------------------------- 10k smoke --
@pytest.mark.slow
def test_10k_dialogue_scale_smoke():
    """The headline streaming regime: 10k dialogues flow through a bounded
    window on a 64-agent analytic cluster with routing-time attribution."""
    cluster = SimCluster(n_agents=64, seed=0, engine_mode="analytic",
                         max_new_tokens=4)
    router = IEMASRouter(cluster.agent_infos(), solver="dense", n_hubs=4,
                         warm_start=True)
    spec = WorkloadSpec("coqa_like", n_dialogues=10_000, seed=1)
    out = EventSimulator(cluster, router, iter_dialogues(spec),
                         arrivals=PoissonArrivals(rate=64.0, seed=2),
                         batch_cap=64, batch_window=0.05, max_inflight=256,
                         profiler=RoutingProfiler(), lean=True,
                         max_new_tokens=4, max_events=20_000_000,
                         max_rounds=2_000_000).run()
    assert out["dialogues_arrived"] == 10_000
    assert out["dialogues_completed"] == 10_000
    assert out["unfinished_dialogues"] == 0 and not out["truncated"]
    assert out["peak_inflight"] <= 256
    assert 0 < out["routing"]["routing_wall_s"] < out["wall_time_s"]
