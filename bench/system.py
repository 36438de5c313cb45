"""The system under test, built from one configuration and one traffic mix.

This is the only module of the benchmark that drives the program
(``repro``). It builds the served path that the window drives:

    EventSimulator -> SimCluster -> make_router(RouterConfig(...))
                   -> AgentEngine (or AnalyticEngine) per agent

Everything model-specific comes from the files that the configuration's
``engine.family`` names: the program's model config from
`adapters/<family>.py`, and the weights (and, for the harness, the counts
and the check) from `reference/<family>.py`. Agent ``i``'s weights are
made on ``devices[i % len(devices)]``, the cell's own chips; its engine's
programs run where their inputs are committed, so its session caches
follow the weights there.

It wraps four of the program's calls from outside, without editing it:
``router.route_batch``, ``router.on_complete``, ``cluster.execute`` and each
engine's ``serve``. The wrappers record host spans (wall clock, and a
``jax.profiler.TraceAnnotation`` of the same name in traced runs), the
routed markets that the correctness check compares, and each served
request's record.
"""
from __future__ import annotations

import gc
import time
from contextlib import nullcontext

import numpy as np

import loader

#: host span names (also the TraceAnnotation names in traced runs)
ROUTE, FEEDBACK, SERVE, LOOP = "bench.route", "bench.feedback", \
    "bench.serve", "bench.loop"


class Recorder:
    """Everything the window produced, as the harness reads it."""

    def __init__(self):
        self.annotate = False       # emit TraceAnnotations (traced runs)
        self.active = False         # inside the measured window
        self.spans: list = []       # (name, t0, t1) inside the window
        self.route_calls: list = []  # per route_batch call (its market)
        self.served: list = []      # per served request (its record)
        self.failed = 0             # dispatches whose engine failed
        self.compiles: list = []    # calls that grew a jit cache
        # from the start of set-up, for the Phase-1 replay: ("route",
        # sessions, prompts, agents, slots, recurrent) per routed market and
        # ("done", agent, session, prompt) per completion the router took
        self.events: list = []

    def span(self, name: str):
        if not self.annotate:
            return nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


class System:
    """One cell's cluster, router and event loop, with the bench's wrappers."""

    def __init__(self, config: dict, traffic: dict, seed: int, devices: list,
                 hook=None):
        from repro.configs.iemas_cluster import RouterConfig, agent_profiles
        from repro.serving import SimCluster, make_router
        from repro.serving.simulator import EventSimulator, RoutingProfiler
        from repro.serving.workload import DialogueScript

        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices = list(devices)
        self.rec = Recorder()
        engine = config["engine"]
        n_agents = int(config["agents"])
        max_new = int(traffic["max_new_tokens"])
        profiles = agent_profiles(n_agents)
        if engine["kind"] == "model":
            self.family = loader.reference(engine["family"])
            self.model_cfg = loader.adapter(engine["family"]).program_config(
                config)
            self.vocab = int(config["vocab_size"])
            self.cluster = SimCluster(
                n_agents=n_agents, seed=0, max_new_tokens=max_new,
                engine_config=self.model_cfg, profiles=profiles)
            self._install_weights()
        elif engine["kind"] == "analytic":
            self.model_cfg = None
            self.family = None
            self.vocab = int(engine["vocab"])
            self.cluster = SimCluster(
                n_agents=n_agents, seed=0, max_new_tokens=max_new,
                engine_mode="analytic", vocab=self.vocab, profiles=profiles)
        else:
            raise ValueError(f"unknown engine kind {engine['kind']!r}")
        # the clients' Eq.-1 valuation, where the configuration states one
        extra = {}
        if "valuation" in config:
            from repro.core.valuation import ValuationConfig

            extra["valuation"] = ValuationConfig(**config["valuation"])
        self.router = make_router(self.cluster,
                                  RouterConfig(**config["router"]), **extra)
        self.profiler = RoutingProfiler()
        gen = loader.family(traffic["family"])
        scripts = (DialogueScript(did, dom, turns, diff) for did, dom, turns,
                   diff in gen.dialogues(traffic, seed, self.vocab))
        arrivals = loader.arrivals().make(traffic["arrivals"], seed)
        self.sim = EventSimulator(
            self.cluster, self.router, scripts, arrivals=arrivals,
            batch_cap=int(traffic["batch_cap"]),
            batch_window=float(traffic["batch_window"]),
            max_inflight=int(traffic["max_inflight"]),
            max_new_tokens=max_new, profiler=self.profiler)
        self.clock = 0.0            # virtual time the loop has reached
        if hook is not None:
            hook(self)              # the checks plant faults in the program
        self._wrap()

    # ---------------- weights (made by the benchmark, from the seed) -----
    def _install_weights(self) -> None:
        """Replace each engine's own initial weights by the benchmark's,
        made from ``--seed`` in one jitted call per agent, on agent
        ``index``'s device ``devices[index % len(devices)]``."""
        import jax

        for index, rt in enumerate(self.cluster.agents.values()):
            eng = rt.engine
            want = jax.tree.map(lambda a: (a.shape, a.dtype), eng.params)
            eng.params = None           # free the engine's own weights first
            gc.collect()
            device = self.devices[index % len(self.devices)]
            params = self.family.make_params(self.config, self.seed, index,
                                             device=device)
            got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
            if got != want:
                raise ValueError("benchmark weights do not match the "
                                 "engine's parameter layout")
            eng.params = params
        jax.block_until_ready([rt.engine.params
                               for rt in self.cluster.agents.values()])

    def warm_engines(self) -> None:
        """Compile the engine programs for this traffic's shape buckets."""
        if self.model_cfg is None:
            return
        for rt in self.cluster.agents.values():
            rt.engine.warmup(
                prefill_buckets=tuple(self.traffic["prefill_buckets"]),
                extend_buckets=())
            # extends on top of one short prefill, so the session stays
            # inside the engine's cache length while every bucket compiles
            first = min(self.traffic["prefill_buckets"])
            for b in self.traffic["extend_buckets"]:
                rt.engine.warmup(prefill_buckets=(first,),
                                 extend_buckets=(b,))

    def engine_programs(self) -> int:
        """Compiled-program count of the engines' jitted calls."""
        if self.model_cfg is None:
            return 0
        eng = next(iter(self.cluster.agents.values())).engine
        return sum(f._cache_size() for f in
                   (eng._prefill_j, eng._extend_j, eng._decode_j,
                    eng._generate_j))

    def placement(self) -> dict:
        """The devices (ids) that hold each agent's weights and session
        caches: {agent: {"weights": [...], "sessions": [...]}}."""
        import jax

        def ids(tree) -> list:
            return sorted({d.id for a in jax.tree.leaves(tree)
                           for d in a.devices()})

        if self.model_cfg is None:
            return {}
        return {aid: {"weights": ids(rt.engine.params),
                      "sessions": ids([s.cache for s in
                                       rt.engine.sessions.values()])}
                for aid, rt in self.cluster.agents.items()}

    # ---------------- wrappers ----------------
    def _wrap(self) -> None:
        rec, router, cluster = self.rec, self.router, self.cluster
        route, feedback, execute = (router.route_batch, router.on_complete,
                                    cluster.execute)
        fused_step = router._fused.step if router._fused is not None else None
        market = {}
        sent, agent_of = {}, {}     # request id -> (session, prompt), agent

        def route_batch(requests, telemetry, free_slots=None):
            live = [a.agent_id for a in router.agents
                    if a.agent_id not in router.quarantined]
            market.clear()
            before = router._fused.cache_size() if fused_step else 0
            with rec.span(ROUTE):
                t0 = self._route_t0 = time.perf_counter()
                out = route(requests, telemetry, free_slots=free_slots)
                t1 = time.perf_counter()
            if fused_step and router._fused.cache_size() != before:
                # the bucket that compiled: batch and market size, the
                # largest column count and the ledger arena's shape
                caps = market.get("caps") or []
                rec.compiles.append({
                    "where": "fused", "in_window": rec.active,
                    "n": len(requests), "m": len(live),
                    "cmax": min(max(caps, default=0), len(requests)),
                    "ledger": list(router.ledger.store.tokens.shape),
                    "seconds": t1 - t0})
            if rec.active:
                rec.spans.append((ROUTE, t0, t1))
                rec.route_calls.append({
                    "t0": t0, "t1": t1, "n": len(requests), "m": len(live),
                    "prompt_tokens": sum(len(r.tokens) for r in requests),
                    "live": live, "values": _f32(market.get("values")),
                    "costs": _f32(market.get("costs")),
                    "affinity": _f32(market.get("affinity")),
                    "event": market.get("event"),
                    "caps": market.get("caps"),
                    "agents": [d.agent_id for d in out],
                    "payments": [float(d.payment) for d in out]})
            for d in out:
                if d.agent_id is not None:
                    agent_of[d.request.request_id] = d.agent_id
            return out

        def step(requests, live, telemetry, caps, start_prices=None):
            sessions = [_session(r) for r in requests]
            rec.events.append(("route", sessions,
                               [r.tokens for r in requests],
                               [a.agent_id for a in live],
                               [a.cache_slots for a in live],
                               [a.recurrent for a in live]))
            out = fused_step(requests, live, telemetry, caps,
                             start_prices=start_prices)
            # out[4] is the Eq.-5 feature tensor; feature 2 is the affinity
            market.update(values=out[3], costs=out[1], caps=list(caps),
                          affinity=out[4][..., 2],
                          event=len(rec.events) - 1)
            for r in requests:
                sent[r.request_id] = (_session(r), r.tokens)
            return out

        def on_complete(request_id, obs):
            with rec.span(FEEDBACK):
                t0 = time.perf_counter()
                feedback(request_id, obs)
                t1 = time.perf_counter()
            if rec.active:
                rec.spans.append((FEEDBACK, t0, t1))
            routed = sent.pop(request_id, None)
            if routed is not None and not obs.failed:
                agent = agent_of.pop(request_id, None)
                if agent is not None:
                    rec.events.append(("done", agent, *routed))

        def execute_(decision, router_):
            out = execute(decision, router_)
            if rec.active and out is not None and out.failed:
                rec.failed += 1
            if rec.active and out is not None and not out.failed:
                last = self._last_serve
                rec.served.append({
                    "agent": out.agent_id, "cost": float(out.cost),
                    "n_prompt": int(out.n_prompt), "n_hit": int(out.n_hit),
                    "n_gen": int(out.n_gen),
                    "prompt": np.asarray(out.request.tokens, np.int32),
                    "output": np.asarray(out.output_tokens, np.int32),
                    "route_t0": self._route_t0, **last})
            return out

        router.route_batch = route_batch
        router.on_complete = on_complete
        cluster.execute = execute_
        if fused_step is not None:
            router._fused.step = step
        # the route call's start stamps every request it dispatches (TTFT)
        self._route_t0 = 0.0
        self._last_serve = {}
        for rt in cluster.agents.values():
            rt.engine.serve = self._wrap_serve(rt.engine, rt.profile.speed)

    def _wrap_serve(self, engine, speed: float):
        rec, serve = self.rec, engine.serve

        def wrapped(*args, **kw):
            before = self.engine_programs()
            with rec.span(SERVE):
                t0 = time.perf_counter()
                res = serve(*args, **kw)
                t1 = time.perf_counter()
            if self.engine_programs() != before:
                rec.compiles.append({
                    "where": "engine", "in_window": rec.active,
                    "n_prompt": res.n_prompt, "n_hit": res.n_hit,
                    "seconds": t1 - t0})
            if rec.active:
                rec.spans.append((SERVE, t0, t1))
            # the engine reports seconds divided by its agent's speed; the
            # wall clock is that times the speed
            self._last_serve = {
                "serve_t0": t0, "serve_t1": t1,
                "first_token_t": t1 - (res.total_time - res.ttft) * speed,
                "ttft_s": res.ttft * speed, "serve_s": res.total_time * speed}
            return res

        return wrapped

    # ---------------- driving the loop ----------------
    def advance(self, dt: float) -> None:
        """Process the events of the next ``dt`` virtual seconds."""
        self.clock += dt
        with self.rec.span(LOOP):
            self.sim.advance_until(self.clock)
        if self.sim._stopped:
            raise RuntimeError(f"the event loop stopped: "
                               f"{self.sim._truncated_reason}")

    def warm(self, requests: int) -> None:
        """Run the stream until ``requests`` more requests were served."""
        done = self.cluster.records
        target = len(done) + int(requests)
        dt = float(self.traffic["batch_window"])
        while len(done) < target:
            self.advance(dt)

    def window(self, seconds: float, mark=None, mark_after: float = 0.0
               ) -> tuple:
        """Drive the stream for ``seconds`` of wall clock; returns the
        window's (start, end) on the host clock. ``mark()`` is called once,
        between two steps, after ``mark_after`` seconds (the end of a traced
        part of the window)."""
        rec = self.rec
        dt = float(self.traffic["batch_window"])
        rec.active = True
        t0 = time.perf_counter()
        end = t0 + seconds
        while time.perf_counter() < end:
            self.advance(dt)
            if mark is not None and time.perf_counter() >= t0 + mark_after:
                mark()
                mark = None
        if mark is not None:
            mark()
        t1 = time.perf_counter()
        rec.active = False
        return t0, t1

    def free(self) -> None:
        """Drop every device array the program holds (weights, caches)."""
        for rt in self.cluster.agents.values():
            eng = rt.engine
            if hasattr(eng, "params"):
                eng.params = None
            eng.sessions.clear()
        self.sim = self.router = self.cluster = None
        gc.collect()


def _session(request):
    """The ledger's session key of a request (its dialogue's, by default)."""
    return request.meta.get("session", request.dialogue_id)


def _f32(a):
    """The router's float32 device results, held without loss."""
    return None if a is None else np.asarray(a, np.float32)
