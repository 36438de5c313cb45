"""The serving stack's tracer (`RoutingProfiler`): ``iemas.*`` spans on the
profiler trace's clock, the counters they carry against ground truth, no
cost when no trace is collected, and named scopes in the fused program."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import IEMASRouter
from repro.serving import (EventSimulator, PoissonArrivals, RoutingProfiler,
                           SimCluster, WorkloadSpec, iter_dialogues)
from repro.serving import simulator as simulator_mod

FUSED = dict(solver="dense-jax", n_hubs=1, warm_start=True, fused=True)


def _host_spans(path: str) -> list:
    """(name, start, end, stats) of every ``iemas.*`` host event, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("iemas."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A small fused-router run over two real engines (one cache slot
    each, so sessions are evicted), traced by the JAX profiler."""
    cfg = get_config("qwen3-8b").scaled(n_layers=1, vocab_size=300)
    cluster = SimCluster(n_agents=2, seed=0, max_new_tokens=2,
                         engine_config=cfg, cache_slots=1)
    router = IEMASRouter(cluster.agent_infos(), **FUSED)
    results = []
    step = router._fused.step

    def recording_step(*args, **kw):
        out = step(*args, **kw)
        results.append(out[5])
        return out

    router._fused.step = recording_step
    prof = RoutingProfiler()
    sim = EventSimulator(
        cluster, router,
        iter_dialogues(WorkloadSpec("coqa_like", n_dialogues=3, seed=5,
                                    vocab=cfg.vocab_size)),
        arrivals=PoissonArrivals(rate=20.0, seed=1), batch_cap=4,
        max_new_tokens=2, profiler=prof)
    out_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        out = sim.run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return dict(cluster=cluster, prof=prof, out=out, results=results,
                spans=_host_spans(path))


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(child, parents) -> bool:
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def test_span_names_and_stats(traced):
    spans = traced["spans"]
    names = {s[0] for s in spans}
    assert {"iemas.route_batch", "iemas.price_book", "iemas.fused_route",
            "iemas.fused.assemble", "iemas.fused.device",
            "iemas.fused.settle", "iemas.phase2_spill",
            "iemas.phase4_feedback", "iemas.engine.serve",
            "iemas.engine.prefill", "iemas.engine.extend",
            "iemas.engine.decode"} <= names
    want = {"iemas.route_batch": {"batch", "n", "m"},
            "iemas.fused.device": {"rounds", "warm", "fallback",
                                   "retraces"},
            "iemas.phase4_feedback": {"req", "n_prompt", "n_hit",
                                      "promised"},
            "iemas.engine.serve": {"session", "batch", "mode", "n_prompt",
                                   "n_hit", "n_gen", "evicted"},
            "iemas.engine.decode": {"steps", "syncs"}}
    for name, keys in want.items():
        for s in _named(spans, name):
            assert set(s[3]) == keys, (name, s[3])
    # every span the profiler closed is in the trace, and no more
    prof = traced["prof"]
    for name in names:
        assert len(_named(spans, name)) == prof.calls[name[len("iemas."):]]


def test_spans_nest(traced):
    spans = traced["spans"]
    routes = _named(spans, "iemas.route_batch")
    fused = _named(spans, "iemas.fused_route")
    serves = _named(spans, "iemas.engine.serve")
    for s in spans:
        if s[0] in ("iemas.price_book", "iemas.fused_route",
                    "iemas.phase2_spill"):
            assert _inside(s, routes), s[:3]
        elif s[0].startswith("iemas.fused."):
            assert _inside(s, fused), s[:3]
        elif s[0] in ("iemas.engine.prefill", "iemas.engine.extend",
                      "iemas.engine.decode"):
            assert _inside(s, serves), s[:3]
    # each fused call is assemble -> device -> settle, in that order
    parts = [s[0] for s in spans if s[0].startswith("iemas.fused.")]
    assert parts == ["iemas.fused.assemble", "iemas.fused.device",
                     "iemas.fused.settle"] * len(fused)


def test_engine_spans_share_the_routing_call_identifier(traced):
    spans = traced["spans"]
    routes = _named(spans, "iemas.route_batch")
    assert [s[3]["batch"] for s in routes] == list(range(1, len(routes) + 1))
    for serve in _named(spans, "iemas.engine.serve"):
        last = max((r for r in routes if r[1] < serve[1]),
                   key=lambda r: r[1])
        assert serve[3]["batch"] == last[3]["batch"]


def test_counters_equal_ground_truth(traced):
    cluster, prof, spans = traced["cluster"], traced["prof"], traced["spans"]
    records = cluster.records
    serves = [s[3] for s in _named(spans, "iemas.engine.serve")]
    assert len(serves) == len(records) == traced["out"]["n"]
    # evictions: the spans' sum is the engines' own count, and some happened
    evicted = sum(rt.engine.evictions for rt in cluster.agents.values())
    assert evicted > 0
    assert sum(s["evicted"] for s in serves) == evicted
    assert prof.counters["engine.serve.evicted"] == evicted
    # every served request is in exactly one mode
    modes = {k: v for k, v in prof.counters.items()
             if k.startswith("engine.serve.mode.")}
    assert sum(modes.values()) == len(records)
    assert modes == {f"engine.serve.mode.{m}": n for m, n in
                     zip(*np.unique([s["mode"] for s in serves],
                                    return_counts=True))}
    # the engine's and the feedback's hit counts are the records' own
    assert sorted((s["session"], s["n_prompt"], s["n_hit"]) for s in serves) \
        == sorted((r.request.dialogue_id, r.n_prompt, r.n_hit)
                  for r in records)
    feedback = {s[3]["req"]: s[3] for s in
                _named(spans, "iemas.phase4_feedback")}
    for r in records:
        assert feedback[r.request.request_id]["n_hit"] == r.n_hit
        assert 0 <= feedback[r.request.request_id]["promised"] <= r.n_prompt
    assert prof.counters["phase4_feedback.n_hit"] == sum(
        r.n_hit for r in records)
    # affinity routing promised hits that the engines kept
    assert prof.counters["phase4_feedback.promised"] > 0
    # bid rounds: each device span's count is its packaged AuctionResult's
    rounds = [s[3]["rounds"] for s in _named(spans, "iemas.fused.device")]
    assert rounds == [res.solver_stats["rounds"]
                      for res in traced["results"]]
    decode = [s[3] for s in _named(spans, "iemas.engine.decode")]
    assert sum(s["steps"] for s in decode) == sum(r.n_gen for r in records)
    # the decode loop runs on the device: one read-back per request
    assert sum(s["syncs"] for s in decode) == len(decode) == len(records)


def _analytic_run(profiler):
    cluster = SimCluster(n_agents=3, seed=4, max_new_tokens=3,
                         engine_mode="analytic", cache_slots=2)
    router = IEMASRouter(cluster.agent_infos(), **FUSED)
    EventSimulator(cluster, router,
                   iter_dialogues(WorkloadSpec("coqa_like", n_dialogues=5,
                                               seed=2)),
                   arrivals=PoissonArrivals(rate=10.0, seed=3), batch_cap=4,
                   max_new_tokens=3, profiler=profiler).run()
    return [(r.request.request_id, r.agent_id, r.payment, r.n_hit)
            for r in cluster.records]


def test_untraced_profiler_builds_no_annotation_and_changes_nothing(
        monkeypatch):
    checks = []

    class NoAnnotation:
        @staticmethod
        def is_enabled():
            checks.append(1)
            return False

        def __init__(self, *args, **kw):
            raise AssertionError("TraceAnnotation built with no trace")

    monkeypatch.setattr(simulator_mod, "TraceAnnotation", NoAnnotation)
    prof = RoutingProfiler()
    with_profiler = _analytic_run(prof)
    # one is_enabled() check per span, and nothing else
    assert len(checks) == sum(prof.calls.values()) > 0
    assert with_profiler == _analytic_run(None)


def test_fused_program_carries_stage_scopes():
    from repro.core.mechanism import Request
    from repro.serving.workload import generate

    cluster = SimCluster(n_agents=3, seed=0, engine_mode="analytic")
    router = IEMASRouter(cluster.agent_infos(), **FUSED)
    requests = [Request(f"r{i}", d.dialogue_id, d.turns[0], 0,
                        domain=d.domain) for i, d in enumerate(generate(
                            WorkloadSpec("coqa_like", n_dialogues=4)))]
    live = router.agents
    prog, args, static, _, _ = router._fused._assemble(
        requests, live, cluster.telemetry.snapshot(0.0),
        [a.capacity for a in live], None)
    text = prog.lower(*args, **static).as_text(debug_info=True)
    for stage in ("affinity", "predict", "auction"):
        assert f"iemas.fused/{stage}" in text, stage
