"""Simulated heterogeneous serving cluster with a virtual clock.

Replaces the paper's asyncio/vLLM deployment (no network stack in this
container) while keeping every quantity the mechanism consumes MEASURED:
engines run real JAX compute; the cluster adds queueing, heterogeneous
hardware speeds, stragglers and failures on a deterministic virtual clock.

Fault tolerance (required at 1000+-node scale):
  * agent failure  -> request marked failed, agent quarantined, request
                      re-enqueued and re-auctioned next round;
  * recovery       -> quarantined agents reinstate after a cooldown;
  * stragglers     -> per-agent slowdown spikes; the router's latency
                      predictor learns them and prices them out (the paper's
                      own mechanism IS the mitigation — measured in tests);
  * elastic scale  -> add_agent/remove_agent rebuild hubs + predictor pool.

Engine modes: ``engine_mode="real"`` (default) runs the reduced JAX models
(`repro.serving.engine.AgentEngine` — measured compute); ``"analytic"``
swaps in `repro.serving.analytic.AnalyticEngine`, whose service times come
from a roofline model calibrated against the real engines, enabling the
128-agent / 10k-dialogue scale runs of `repro.serving.simulator`.
``engine_config`` gives every real engine one model (e.g. a published
config at full width) in place of the per-class reduced ones. Real engines
are placed round-robin over ``jax.devices()`` (`agent_device`), so on a
four-chip host each agent's parameters, caches and jitted calls sit on its
own chip. `SimCluster.dispatch` serves one route batch: where the agents'
weights sit on several devices, one worker thread a device serves that
device's requests while the others' run, all on one virtual clock.

`run_workload` below is the closed-loop, fixed-population oracle loop; the
event-driven open-loop driver for scale runs lives in
`repro.serving.simulator.EventSimulator` and reproduces this loop's
decisions bit-for-bit under synchronous arrivals (tests/test_simulator.py).
"""
from __future__ import annotations

import heapq
import sys
import threading
import warnings
import zlib
from collections import Counter, deque
from concurrent.futures import ThreadPoolExecutor, wait
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field, replace

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.configs.iemas_cluster import (DEFAULT_ROUTER, MODEL_CLASSES,
                                         AgentProfile, RouterConfig,
                                         agent_profiles)
from repro.core.adversary import AdversaryMix, AdversaryPolicy
from repro.core.mechanism import AgentInfo, CompletionObs, IEMASRouter, Request
from repro.core.pricing import TokenPrices
from repro.serving.engine import AgentEngine, weights_devices
from repro.serving.evaluator import SimulatedSkillEvaluator
from repro.serving.telemetry import TelemetryTracker
from repro.serving.workload import DialogueScript
from repro.utils.timing import phase_scope

# the interpreter's thread switch interval while several workers serve: a
# worker whose device has finished takes its clock reading once it has the
# interpreter back, so this bounds how late that reading can come (the
# default, 5 ms, is a fifth of a serve)
_SWITCH_S = 1e-4


def agent_device(index: int, devices: list):
    """Round-robin placement: the ``index``-th real engine a cluster builds
    runs on ``devices[index % len(devices)]`` (one device: all share it)."""
    return devices[index % len(devices)]


def _engine_config(model_class: str, vocab: int):
    import dataclasses

    from repro.configs import get_config

    n_layers, d_model, n_heads, d_ff, _scale = MODEL_CLASSES[model_class]
    base = get_config("qwen3-8b").scaled(dtype="float32")
    return dataclasses.replace(
        base, name=f"engine-{model_class}", n_layers=n_layers, d_model=d_model,
        n_heads=n_heads, n_kv_heads=n_heads, head_dim=d_model // n_heads,
        d_ff=d_ff, vocab_size=vocab + 1, qk_norm=False)


@dataclass
class RequestRecord:
    """Ledger entry for one dispatched request (metrics + turn threading)."""

    request: Request
    agent_id: str
    dispatched_at: float
    ttft: float
    latency: float            # reported TTFT incl. queue + straggler effects
    cost: float
    n_prompt: int
    n_hit: int
    n_gen: int
    quality: float
    payment: float
    welfare_weight: float
    failed: bool = False
    # the engine's generated ids; run_workload threads them into the next
    # turn's prompt (dialogue causality, Appendix C.1)
    output_tokens: np.ndarray = field(
        default_factory=lambda: np.zeros(0, np.int32))


@dataclass(frozen=True)
class _Draws:
    """One dispatch's random draws, taken in decision order: whether it
    fails, its straggle factor and its quality score, and its place among
    same-time completions."""

    failed: bool
    straggle: float
    quality: float
    seq: int


@dataclass
class AgentRuntime:
    """One live agent: published info + engine + fault-injection knobs."""

    info: AgentInfo
    profile: AgentProfile
    engine: AgentEngine
    fail_prob: float = 0.0
    straggle_prob: float = 0.0
    straggle_factor: float = 6.0
    down_until: float = -1.0


class SimCluster:
    """Heterogeneous simulated cluster: engines + queueing + faults on a
    deterministic virtual clock (see module docstring)."""

    def __init__(self, n_agents: int = 9, *, vocab: int = 255, seed: int = 0,
                 max_new_tokens: int = 6, fail_prob: float = 0.0,
                 straggle_prob: float = 0.0, cache_slots: int | None = None,
                 quarantine_cooldown: float = 30.0, warmup: bool = False,
                 engine_mode: str = "real",
                 adversary_mix: AdversaryMix | None = None,
                 profiles: list[AgentProfile] | None = None,
                 engine_config: ModelConfig | None = None):
        if engine_mode not in ("real", "analytic"):
            raise ValueError(f"engine_mode must be real|analytic, "
                             f"got {engine_mode!r}")
        if engine_config is not None and engine_mode != "real":
            raise ValueError("engine_config sets the real engines' model; "
                             "analytic engines price MODEL_CLASSES")
        self.rng = np.random.default_rng(seed)
        self.vocab = vocab
        self.engine_mode = engine_mode
        # every real engine runs this model when given (else one derived
        # from its profile's MODEL_CLASSES entry), placed round-robin over
        # the visible devices in creation order (`agent_device`)
        self.engine_config = engine_config
        self._n_real = 0
        self.telemetry = TelemetryTracker()
        self.evaluator = SimulatedSkillEvaluator(seed=seed + 1)
        self.quarantine_cooldown = quarantine_cooldown
        # attached by serving-layer profilers (repro.serving.simulator):
        # phase() around Phase 4, handed on to every agent's engine
        self.profiler = None
        self.agents: dict[str, AgentRuntime] = {}
        # ``profiles`` overrides the generated population: federated shards
        # pass their partition of the GLOBAL agent_profiles() list so ids,
        # prices and engine seeds match the single-heap fleet exactly
        for prof in (profiles if profiles is not None
                     else agent_profiles(n_agents, seed=seed)):
            self._add_runtime(prof, fail_prob, straggle_prob, cache_slots,
                              max_new_tokens)
        # strategic-agent injection (repro.core.adversary): policies keyed by
        # agent id mutate published profiles / Phase-4 reports / membership;
        # an empty dict (no mix, or fraction 0) is bit-identical honest play
        self.adversaries: dict[str, AdversaryPolicy] = (
            adversary_mix.assign([rt.info for rt in self.agents.values()])
            if adversary_mix is not None else {})
        if warmup:
            for rt in self.agents.values():
                rt.engine.warmup()
        self.records: list[RequestRecord] = []
        self.now = 0.0
        self._completions: list = []  # heap of (time, seq, record, router_obs)
        self._seq = 0
        # concurrent dispatch (`dispatch`): one thread at a time runs host
        # code; the workers' pool is built on the first batch that needs it
        self._host = threading.Lock()
        self._pool: ThreadPoolExecutor | None = None
        self._drawn: dict[int, _Draws] = {}   # id(decision) -> its draws

    def _add_runtime(self, prof: AgentProfile, fail_prob, straggle_prob,
                     cache_slots, max_new_tokens):
        eng_seed = zlib.crc32(prof.agent_id.encode()) % (2**31)
        if self.engine_mode == "analytic":
            from repro.serving.analytic import AnalyticEngine

            engine = AnalyticEngine(
                prof.model_class, vocab=self.vocab, seed=eng_seed,
                speed=prof.speed, cache_slots=cache_slots or prof.cache_slots,
                max_new_tokens=max_new_tokens)
        else:
            cfg = self.engine_config or _engine_config(prof.model_class,
                                                       self.vocab)
            engine = AgentEngine(
                cfg, seed=eng_seed, speed=prof.speed,
                cache_slots=cache_slots or prof.cache_slots,
                max_new_tokens=max_new_tokens,
                device=agent_device(self._n_real, jax.devices()))
            self._n_real += 1
        info = AgentInfo(
            agent_id=prof.agent_id,
            prices=TokenPrices(prof.price_miss, prof.price_hit, prof.price_out),
            capacity=prof.capacity, domains=prof.domains, scale=prof.scale,
            recurrent=engine.recurrent, cache_slots=engine.cache_slots)
        engine.profiler = self.profiler
        self.agents[prof.agent_id] = AgentRuntime(
            info, prof, engine, fail_prob=fail_prob,
            straggle_prob=straggle_prob)

    # ---------------- elastic membership ----------------
    def agent_infos(self) -> list[AgentInfo]:
        """Published AgentInfo profiles of every live runtime.

        Strategic agents publish through their policy (a mutated COPY —
        e.g. misreported prices); everyone else publishes their true
        ``rt.info`` object itself, preserving the seed behavior where the
        router and cluster share one AgentInfo instance."""
        out = []
        for aid, rt in self.agents.items():
            pol = self.adversaries.get(aid)
            out.append(pol.publish(rt.info) if pol is not None else rt.info)
        return out

    def add_agent(self, profile: AgentProfile, router=None) -> None:
        """Elastic scale-out: spin up a runtime (and tell the router)."""
        self._add_runtime(profile, 0.0, 0.0, None, 6)
        if router is not None and hasattr(router, "add_agent"):
            router.add_agent(self.agents[profile.agent_id].info)

    def remove_agent(self, agent_id: str, router=None) -> None:
        """Elastic scale-in: drop a runtime (and tell the router)."""
        self.agents.pop(agent_id, None)
        if router is not None and hasattr(router, "remove_agent"):
            router.remove_agent(agent_id)

    def adversary_tick(self, router) -> None:
        """Give every strategic agent its per-round action hook (churn
        policies flap membership/capacity/quarantine here).  A no-op when
        no adversaries are assigned, so honest serving loops keep their
        bit-exact lockstep parity."""
        if not self.adversaries:
            return
        for aid, pol in list(self.adversaries.items()):
            pol.tick(self, router, aid)

    # ---------------- serving rounds ----------------
    def free_slots(self) -> dict:
        """Per-agent free concurrency slots (capacity minus inflight)."""
        inflight = self.telemetry.agent_inflight
        return {aid: max(0, rt.info.capacity - inflight.get(aid, 0))
                for aid, rt in self.agents.items()}

    def _draw(self, decision) -> _Draws:
        """Failure injection, straggling and the quality score of one
        dispatch to a live agent, drawn from the cluster's generators in the
        order serial dispatch draws them; a failure quarantines the agent at
        once, so the batch's later requests to it fail too."""
        rt = self.agents[decision.agent_id]
        seq = self._seq
        self._seq += 1
        if rt.down_until > self.now or self.rng.random() < rt.fail_prob:
            rt.down_until = max(rt.down_until,
                                self.now + self.quarantine_cooldown)
            return _Draws(True, 1.0, 0.0, seq)
        straggle = (rt.straggle_factor
                    if self.rng.random() < rt.straggle_prob else 1.0)
        req = decision.request
        quality = self.evaluator.score(
            rt.info.scale, req.domain in rt.info.domains,
            float(req.meta.get("difficulty", 0.5)))
        return _Draws(False, straggle, quality, seq)

    def execute(self, decision, router) -> RequestRecord | None:
        """Dispatch one routed request to its agent and schedule completion."""
        req = decision.request
        if decision.agent_id is None or decision.agent_id not in self.agents:
            return None
        rt = self.agents[decision.agent_id]
        self.telemetry.on_dispatch(rt.info.agent_id, self.now)
        draws = self._drawn.pop(id(decision), None) or self._draw(decision)

        if draws.failed:
            rec = RequestRecord(req, rt.info.agent_id, self.now, 0.0, 0.0, 0.0,
                                len(req.tokens), 0, 0, 0.0, 0.0,
                                decision.welfare_weight, failed=True)
            obs = CompletionObs(0.0, len(req.tokens), 0, 0, 0.0, failed=True)
            heapq.heappush(self._completions,
                           (self.now + 0.05, draws.seq, rec, obs))
            return rec

        # DAG steps serve under their own session key with parent-session
        # fork candidates (handoff prefix reuse); linear requests carry no
        # such meta and serve under the dialogue id exactly as before.
        session = req.meta.get("session", req.dialogue_id)
        result = rt.engine.serve(session, req.tokens, now=self.now,
                                 max_new_tokens=req.max_new_tokens,
                                 parents=req.meta.get("parent_sessions", ()))
        queue = self.telemetry.agent_inflight.get(rt.info.agent_id, 1) - 1
        latency = result.ttft * draws.straggle + 0.001 * max(0, queue)
        total = result.total_time * draws.straggle + 0.001 * max(0, queue)
        quality = draws.quality

        cost = (rt.info.prices.miss * (result.n_prompt - result.n_hit)
                + rt.info.prices.hit * result.n_hit
                + rt.info.prices.out * result.n_gen)
        rec = RequestRecord(req, rt.info.agent_id, self.now, result.ttft,
                            latency, cost, result.n_prompt, result.n_hit,
                            result.n_gen, quality, decision.payment,
                            decision.welfare_weight,
                            output_tokens=result.output_tokens)
        obs = CompletionObs(latency, result.n_prompt, result.n_hit,
                            result.n_gen, quality)
        if self.adversaries:
            # adversarial run: every Phase-4 report flows through a policy
            # (strategic agents may lie; honest ones attach the audit truth,
            # whose zero residual is reputation-neutral by construction)
            pol = self.adversaries.get(rt.info.agent_id)
            obs = (pol.report(obs, quality) if pol is not None
                   else replace(obs, audit_quality=quality))
        self.telemetry.on_busy(rt.info.agent_id, total)
        heapq.heappush(self._completions,
                       (self.now + total, draws.seq, rec, obs))
        return rec

    def dispatch(self, decisions, router) -> list:
        """Execute one route batch's decisions; returns each one's
        `execute` result (None for a dead dispatch target), in order.

        The decisions are grouped by the devices that hold their agents'
        weights. With one such group every request runs in the caller's
        thread, one after another. With several, each group runs on a
        worker of a pool with at most one worker a device, in decision
        order, while the caller runs the requests of agents without weights
        (analytic engines, dead targets); the random draws are taken in
        decision order first, so the same requests fail whichever device
        finishes first. A worker holds the host lock for the whole of each
        `execute` and lets go only while its engine waits on the device
        (`AgentEngine.wait`), so the cluster's state, the profiler and
        anything wrapping `execute` or `serve` see one request at a time;
        meanwhile the interpreter switches threads every `_SWITCH_S`.
        Every request has been served when this returns; a worker's
        exception is raised here.

        Profiler span ``dispatch`` (counters ``chips``, the device groups,
        and ``requests``), where there is a request to serve."""
        if not decisions:
            return []
        groups: dict[frozenset, list[int]] = {}
        for i, dec in enumerate(decisions):
            rt = self.agents.get(dec.agent_id)
            key = weights_devices(rt.engine) if rt is not None else frozenset()
            groups.setdefault(key, []).append(i)
        local = groups.pop(frozenset(), [])
        with phase_scope(self.profiler, "dispatch") as span:
            if len(groups) <= 1:
                out = [self.execute(dec, router) for dec in decisions]
            else:
                out = self._dispatch_groups(decisions, local,
                                            list(groups.values()), router)
            span.set(chips=len(groups), requests=len(decisions))
        return out

    def _dispatch_groups(self, decisions, local: list, groups: list,
                         router) -> list:
        """`dispatch` over several device groups (its docstring)."""
        for dec in decisions:
            if dec.agent_id in self.agents:
                self._drawn[id(dec)] = self._draw(dec)
        out = [None] * len(decisions)
        engines = {self.agents[decisions[i].agent_id].engine
                   for idx in groups for i in idx}
        if self._pool is None:
            devices = set().union(*(weights_devices(rt.engine)
                                    for rt in self.agents.values()))
            self._pool = ThreadPoolExecutor(len(devices),
                                            thread_name_prefix="dispatch")
        for eng in engines:
            eng.wait = self._device_wait
        interval = sys.getswitchinterval()
        sys.setswitchinterval(min(interval, _SWITCH_S))
        try:
            for i in local:
                out[i] = self.execute(decisions[i], router)
            futures = [self._pool.submit(self._serve_group, decisions, idx,
                                         router, out) for idx in groups]
            wait(futures)
            for f in futures:
                f.result()
        finally:
            sys.setswitchinterval(interval)
            for eng in engines:
                eng.wait = nullcontext
            self._drawn.clear()
        return out

    def _serve_group(self, decisions, idx: list, router, out: list) -> None:
        """One worker's share of a batch: its device's requests, in order."""
        for i in idx:
            with self._host:
                out[i] = self.execute(decisions[i], router)

    @contextmanager
    def _device_wait(self):
        """An engine's wait on its device: the host lock is free meanwhile."""
        self._host.release()
        try:
            yield
        finally:
            self._host.acquire()

    def next_completion_time(self) -> float | None:
        """Virtual time of the earliest scheduled completion (event hook)."""
        return self._completions[0][0] if self._completions else None

    def advance(self, dt: float, router) -> list[RequestRecord]:
        """Advance the virtual clock by ``dt``, delivering completions."""
        return self.advance_to(self.now + dt, router)

    def advance_to(self, t: float, router) -> list[RequestRecord]:
        """Advance the clock to absolute virtual time ``t`` (>= now),
        delivering every completion due by then to the router.

        The event simulator jumps straight to the next event with this hook
        (setting ``now`` exactly, no float drift against heap timestamps);
        the closed-loop ``advance`` above is a thin wrapper.
        """
        self.now = max(self.now, float(t))
        done = []
        while self._completions and self._completions[0][0] <= self.now:
            _, _, rec, obs = heapq.heappop(self._completions)
            self.telemetry.on_complete(rec.agent_id, self.now)
            rid = rec.request.request_id
            with phase_scope(self.profiler, "phase4_feedback",
                             req=rid) as span:
                if self.profiler is not None:
                    # what the router's affinity promised against what the
                    # engine kept, read before on_complete retires it
                    promised = getattr(router, "promised_hit", None)
                    span.set(n_prompt=rec.n_prompt, n_hit=rec.n_hit,
                             promised=promised(rid) if promised else 0)
                router.on_complete(rid, obs)
            if not rec.failed:
                self.records.append(rec)
            done.append(rec)
        # reinstate recovered agents
        if hasattr(router, "reinstate"):
            for aid, rt in self.agents.items():
                if 0 <= rt.down_until <= self.now:
                    router.reinstate(aid)
                    rt.down_until = -1.0
        return done

    # ---------------- metrics ----------------
    def metrics(self) -> dict:
        """Aggregate request-level metrics over completed (non-failed)
        records: KV hit rate, latency, cost, quality."""
        if not self.records:
            return {"n": 0}
        hits = np.array([r.n_hit / max(1, r.n_prompt) for r in self.records])
        lat = np.array([r.latency for r in self.records])
        cost = np.array([r.cost for r in self.records])
        qual = np.array([r.quality for r in self.records])
        return {
            "n": len(self.records),
            "kv_hit_rate": float(hits.mean()),
            "latency_ms_median": float(np.median(lat) * 1e3),
            "latency_ms_mean": float(lat.mean() * 1e3),
            "latency_ms_p95": float(np.percentile(lat, 95) * 1e3),
            "cost_mean": float(cost.mean()),
            "quality_mean": float(qual.mean()),
        }


def make_router(cluster: SimCluster, config: RouterConfig | None = None,
                **overrides) -> IEMASRouter:
    """Build the IEMAS router for a cluster from a RouterConfig.

    ``overrides`` land on top of the config and are passed straight to
    IEMASRouter (e.g. ``solver="dense"``, ``predictor_kw={...}``), so the
    Phase-2 solver choice threads from configs/CLI down to run_auction."""
    kwargs = (config or DEFAULT_ROUTER).router_kwargs()
    kwargs.update(overrides)
    return IEMASRouter(cluster.agent_infos(), **kwargs)


def run_workload(cluster: SimCluster, router, dialogues: list[DialogueScript],
                 *, round_dt: float = 0.05, max_rounds: int = 4000,
                 batch_per_round: int = 16, max_new_tokens: int = 6,
                 on_round=None) -> dict:
    """Drive multi-turn dialogues through router+cluster to completion.

    Dialogue causality: turn t+1 is issued only after turn t completes, with
    the engine's actual answer appended to the conversation (Appendix C.1).

    Fairness: ready dialogues queue through a FIFO deque ordered by when
    their turn became ready — a request skipped by the ``batch_per_round``
    cap keeps its place at the head next round.  (The seed scanned the
    ``state`` dict in insertion order every round and broke at the cap, so
    late-inserted dialogues were starved whenever the ready count exceeded
    it.)  Requests the auction leaves unmatched return to the *front* of
    the queue in order; failed requests re-enter at the back when their
    failure is delivered, like any other newly-ready turn.

    Truncation: exhausting ``max_rounds`` is no longer silent — the result
    carries ``unfinished_dialogues`` / ``completed_turns`` / ``truncated``
    and a ``RuntimeWarning`` fires, so scaled runs cannot quietly drop the
    tail of the latency distribution.  ``dispatched_requests`` and the
    ``requests_per_dialogue_*`` stats attribute dispatch counts (including
    fault-path retries) per dialogue.

    This loop is the closed-loop oracle: `repro.serving.simulator` must
    reproduce its decisions bit-for-bit under synchronous arrivals.
    """
    for d in dialogues:
        if not isinstance(d, DialogueScript):
            raise TypeError(
                f"run_workload drives linear DialogueScripts only; got "
                f"{type(d).__name__} for {getattr(d, 'dialogue_id', '?')!r} — "
                f"DAG workloads need repro.serving.simulator.EventSimulator")
    state = {d.dialogue_id: {"script": d, "turn": 0, "history": np.zeros(0, np.int32),
                             "busy": False} for d in dialogues}
    pending_next: dict[str, np.ndarray] = {
        d.dialogue_id: d.turns[0] for d in dialogues}
    ready: deque[str] = deque(d.dialogue_id for d in dialogues)
    rid = 0
    rounds = 0
    # per-dialogue dispatch attribution (includes fault-path retries); this
    # replaces the seed's write-only record_of dict
    dispatch_count: Counter = Counter()
    dispatched = 0
    while rounds < max_rounds:
        rounds += 1
        # collect up to batch_per_round ready requests (micro-batching,
        # C.2.1), FIFO by readiness time
        batch = []
        while ready and len(batch) < batch_per_round:
            did = ready.popleft()
            st = state[did]
            script = st["script"]
            prompt = np.concatenate([st["history"], pending_next[did]])
            batch.append(Request(request_id=f"r{rid}", dialogue_id=did,
                                 tokens=prompt.astype(np.int32), turn=st["turn"],
                                 domain=script.domain,
                                 max_new_tokens=max_new_tokens,
                                 meta={"difficulty": script.difficulty}))
            rid += 1
        if batch:
            telem = cluster.telemetry.snapshot(cluster.now)
            decisions = router.route_batch(batch, telem,
                                           free_slots=cluster.free_slots())
            records = iter(cluster.dispatch(
                [dec for dec in decisions if dec.agent_id is not None],
                router))
            unmatched = []
            for dec in decisions:
                did = dec.request.dialogue_id
                if dec.agent_id is None:
                    unmatched.append(did)  # retry, keeping queue priority
                    continue
                if next(records) is None:
                    # dead dispatch target (agent removed from the cluster
                    # but not the router): report it as a failure so the
                    # router quarantines it and clears its pending entry,
                    # instead of re-matching the same dead agent forever
                    router.on_complete(dec.request.request_id, CompletionObs(
                        0.0, len(dec.request.tokens), 0, 0, 0.0, failed=True))
                    unmatched.append(did)
                    continue
                state[did]["busy"] = True
                dispatch_count[did] += 1
                dispatched += 1
            ready.extendleft(reversed(unmatched))
        done = cluster.advance(round_dt, router)
        for rec in done:
            did = rec.request.dialogue_id
            st = state[did]
            st["busy"] = False
            if rec.failed:
                ready.append(did)  # re-issue the same turn next round
                continue
            new_user = pending_next.pop(did)
            st["history"] = np.concatenate(
                [st["history"], new_user, rec.output_tokens]).astype(np.int32)
            st["turn"] += 1
            script = st["script"]
            if st["turn"] < len(script.turns):
                pending_next[did] = script.turns[st["turn"]]
                ready.append(did)
        # strategic-agent round hook (no-op without an adversary mix)
        cluster.adversary_tick(router)
        if not pending_next and not any(st["busy"] for st in state.values()):
            break
        if on_round is not None:
            on_round(rounds, cluster)
    out = cluster.metrics()
    out["rounds"] = rounds
    out["completed_turns"] = sum(st["turn"] for st in state.values())
    # a dialogue is unfinished iff a turn of it is still pending (waiting,
    # in the ready queue, or in flight when the round budget ran out)
    out["unfinished_dialogues"] = len(pending_next)
    out["truncated"] = bool(pending_next)
    out["dispatched_requests"] = dispatched
    if dispatch_count:
        # same definition as EventSimulator: mean over dialogues that were
        # actually dispatched (identical when nothing truncated)
        out["requests_per_dialogue_mean"] = dispatched / len(dispatch_count)
        out["requests_per_dialogue_max"] = max(dispatch_count.values())
    if pending_next:
        warnings.warn(
            f"run_workload: round budget ({max_rounds}) exhausted with "
            f"{len(pending_next)}/{len(state)} dialogues unfinished "
            f"({out['completed_turns']} turns completed); metrics cover "
            f"completed requests only", RuntimeWarning, stacklevel=2)
    # warm-start effectiveness (IEMASRouter only): how often a hub's auction
    # was seeded from the previous round's slot prices vs cold-started
    book = getattr(router, "price_book", None)
    if book is not None and getattr(router, "warm_start", False):
        out["warm_start"] = book.stats()
    return out
