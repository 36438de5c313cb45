"""`SimCluster.dispatch`: one route batch served on every device that holds
weights at once, with the same records, failures and wrapper hand-offs as
serial `execute`, no thread where one device holds every agent, at most one
worker a device, and a worker's exception raised to the caller.

Tier 1 sees one CPU device (`test_placement`), so the cases that need
several run once, in a child process with four CPU devices (this file run
as a script); each test reads its case's result.
"""
import json
import os
import subprocess
import sys
import threading
import time
from contextlib import contextmanager
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.mechanism import RouteDecision, Request
from repro.serving import SimCluster

ROOT = Path(__file__).resolve().parents[1]
N_AGENTS, MAX_NEW, VOCAB = 4, 2, 300


def _cluster(weights_on=None, **kw) -> SimCluster:
    """Four tiny qwen3 agents, agent ``i`` built on device ``i mod n``;
    ``weights_on[i]`` moves agent ``i``'s weights to that device, as the
    benchmark's weight install does, leaving its ``engine.device``."""
    cfg = get_config("qwen3-8b").scaled(n_layers=1, vocab_size=VOCAB)
    cluster = SimCluster(n_agents=N_AGENTS, seed=0, max_new_tokens=MAX_NEW,
                         engine_config=cfg, **kw)
    if weights_on is not None:
        devices = jax.devices()
        for rt, d in zip(cluster.agents.values(), weights_on):
            rt.engine.params = jax.device_put(rt.engine.params, devices[d])
    return cluster


class _Router:
    """Takes the completions the cluster delivers, in order."""

    def __init__(self):
        self.completed = []

    def on_complete(self, request_id, obs):
        self.completed.append((request_id, obs.failed))


def _batch(cluster, n: int, tag: str, rng, prompts=None) -> list:
    """``n`` decisions spread over the agents in turn; ``prompts[k]`` (the
    previous turn's prompt and answer) makes request ``k`` a follow-up."""
    agents = list(cluster.agents)
    out = []
    for k in range(n):
        new = rng.integers(1, VOCAB, int(rng.integers(5, 40)), dtype=np.int32)
        tokens = new if prompts is None else np.concatenate([prompts[k], new])
        req = Request(f"{tag}{k}", f"d{k}", tokens, turn=0,
                      max_new_tokens=MAX_NEW, meta={"difficulty": 0.5})
        out.append(RouteDecision(req, agents[k % len(agents)], 1.0, None,
                                 1.0, 0))
    return out


def _serve(cluster, batches: int, n: int, concurrent: bool, seed: int = 0):
    """``batches`` route batches of ``n`` follow-ups each through one
    cluster, by `dispatch` or by serial `execute`; one virtual second
    between batches. Returns the records and the router."""
    rng = np.random.default_rng(seed)
    router, records, prompts = _Router(), [], None
    for b in range(batches):
        decisions = _batch(cluster, n, f"b{b}r", rng, prompts)
        recs = (cluster.dispatch(decisions, router) if concurrent
                else [cluster.execute(d, router) for d in decisions])
        records += recs
        prompts = [np.concatenate([d.request.tokens, r.output_tokens])
                   for d, r in zip(decisions, recs)]
        cluster.advance_to(cluster.now + 1.0, router)
    return records, router


def _fields(records) -> list:
    return [(r.request.request_id, r.agent_id, r.failed, r.n_prompt, r.n_hit,
             r.n_gen, round(float(r.cost), 9), round(float(r.quality), 9),
             [int(t) for t in r.output_tokens]) for r in records]


def case_same_records() -> dict:
    """(a) dispatch over four devices == serial execute, field by field."""
    from repro.serving.simulator import RoutingProfiler

    concurrent = _cluster()
    prof = RoutingProfiler()
    concurrent.profiler = prof
    got, _ = _serve(concurrent, 2, 12, True)
    want, _ = _serve(_cluster(), 2, 12, False)
    return {"equal": _fields(got) == _fields(want),
            "hits": sum(r.n_hit for r in got),
            "chips": prof.counters.get("dispatch.chips"),
            "requests": prof.counters.get("dispatch.requests")}


def case_wrapper_handoff() -> dict:
    """(b) a wrapper on `execute` reads what a wrapper on `serve` stored on
    one shared attribute: always its own serve's value."""
    cluster = _cluster()
    shared = {"last": None}
    for rt in cluster.agents.values():
        serve = rt.engine.serve

        def wrapped(session, prompt, *args, _serve=serve, **kw):
            res = _serve(session, prompt, *args, **kw)
            shared["last"] = (session, [int(t) for t in res.output_tokens])
            time.sleep(0)       # let another thread in, if one may run
            return res

        rt.engine.serve = wrapped
    execute, seen, wrong = cluster.execute, [], []

    def execute_(decision, router):
        rec = execute(decision, router)
        own = (decision.request.dialogue_id,
               [int(t) for t in rec.output_tokens])
        seen.append(threading.get_ident())
        if shared["last"] != own:
            wrong.append(decision.request.request_id)
        return rec

    cluster.execute = execute_
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        records, _ = _serve(cluster, 6, 16, True, seed=1)
    finally:
        sys.setswitchinterval(interval)
    return {"served": len(records), "wrong": wrong,
            "threads": len(set(seen))}


def case_one_device() -> dict:
    """(c) every agent's weights on device 0 (their engines built on 0-3):
    the caller's thread serves the batch; no pool, no thread."""
    cluster = _cluster(weights_on=[0, 0, 0, 0])
    before = threading.active_count()
    seen = set()
    execute = cluster.execute

    def execute_(decision, router):
        seen.add(threading.get_ident())
        return execute(decision, router)

    cluster.execute = execute_
    _serve(cluster, 2, 8, True)
    return {"pool": cluster._pool is not None,
            "threads_added": threading.active_count() - before,
            "caller_only": seen == {threading.get_ident()},
            "devices": sorted(rt.engine.device.id
                              for rt in cluster.agents.values())}


def case_failures() -> dict:
    """(d) fail_prob 0.3: concurrent and serial dispatch fail the same
    requests and deliver the failures in the same order."""
    out = []
    for concurrent in (True, False):
        cluster = _cluster(fail_prob=0.3, quarantine_cooldown=0.5)
        records, router = _serve(cluster, 4, 12, concurrent, seed=2)
        failed = {rid for rid, f in router.completed if f}
        out.append((_fields(records),
                    [rid for rid, f in router.completed if f], len(failed)))
    (got, got_order, n), (want, want_order, _) = out
    return {"equal": got == want, "order": got_order == want_order,
            "failed": n, "requests": len(got)}


def case_workers() -> dict:
    """(e) weights on two of four devices: at most two workers, and on
    four devices at most four."""
    out = {}
    for name, on in (("two", [0, 1, 0, 1]), ("four", [0, 1, 2, 3])):
        cluster = _cluster(weights_on=on)
        seen = set()
        execute = cluster.execute

        def execute_(decision, router, execute=execute, seen=seen):
            seen.add(threading.get_ident())
            return execute(decision, router)

        cluster.execute = execute_
        _serve(cluster, 3, 12, True, seed=3)
        out[name] = {"max_workers": cluster._pool._max_workers,
                     "threads": len(seen - {threading.get_ident()})}
    return out


def case_worker_exception() -> dict:
    """(f) one agent's serve raises: dispatch raises it once every worker
    has finished, and the engines' wait hooks are put back."""
    cluster = _cluster()
    bad = list(cluster.agents.values())[2].engine
    inside = {"n": 0, "max_after": 0}

    def broken(*args, **kw):
        raise RuntimeError("planted fault")

    bad.serve = broken
    execute = cluster.execute

    def execute_(decision, router):
        inside["n"] += 1
        try:
            return execute(decision, router)
        finally:
            inside["n"] -= 1

    cluster.execute = execute_
    raised = None
    try:
        cluster.dispatch(_batch(cluster, 12, "x", np.random.default_rng(4)),
                         _Router())
    except RuntimeError as e:
        raised = str(e)
    return {"raised": raised, "inside_after": inside["n"],
            "hooks_reset": all(
                rt.engine.wait.__name__ == "nullcontext"
                for rt in cluster.agents.values()),
            "drawn_left": len(cluster._drawn)}


def case_switch_interval() -> dict:
    """While workers serve, the interpreter switches threads every
    `_SWITCH_S` at most (a worker reads its clock soon after its device
    finishes); afterwards the interval is the caller's again."""
    from repro.serving.cluster import _SWITCH_S

    cluster = _cluster()
    seen = []
    for rt in cluster.agents.values():
        serve = rt.engine.serve

        def wrapped(*args, _serve=serve, **kw):
            seen.append(sys.getswitchinterval())
            return _serve(*args, **kw)

        rt.engine.serve = wrapped
    before = sys.getswitchinterval()
    _serve(cluster, 1, 8, True, seed=5)
    return {"inside": max(seen), "limit": _SWITCH_S, "before": before,
            "after": sys.getswitchinterval(), "served": len(seen)}


def case_simulator() -> dict:
    """The event loop over four devices: every dialogue served, each batch
    one dispatch to the devices its agents hold (the first batch's 16
    requests outnumber an agent's 12 slots, so it goes to two or more)."""
    from repro.core import IEMASRouter
    from repro.serving import (EventSimulator, RoutingProfiler,
                               SyncArrivals, WorkloadSpec, iter_dialogues)

    cluster = _cluster()
    router = IEMASRouter(cluster.agent_infos())
    prof = RoutingProfiler()
    out = EventSimulator(
        cluster, router,
        iter_dialogues(WorkloadSpec("coqa_like", n_dialogues=16, seed=6,
                                    vocab=VOCAB)),
        arrivals=SyncArrivals(), batch_cap=16, max_new_tokens=MAX_NEW,
        profiler=prof).run()
    return {"truncated": out["truncated"], "served": out["n"],
            "dispatched": prof.counters.get("dispatch.requests"),
            "batches": prof.calls.get("dispatch"),
            "chips": prof.counters.get("dispatch.chips")}


CASES = {f.__name__[len("case_"):]: f for f in (
    case_same_records, case_wrapper_handoff, case_one_device, case_failures,
    case_workers, case_worker_exception, case_switch_interval,
    case_simulator)}


@pytest.fixture(scope="module")
def four_devices():
    """Every case's result, from one child process with four CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    run = subprocess.run([sys.executable, __file__], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-4000:]
    return json.loads(run.stdout.strip().splitlines()[-1])


def test_dispatch_gives_the_records_of_serial_execute(four_devices):
    got = four_devices["same_records"]
    assert got["equal"]
    assert got["hits"] > 0                    # the follow-ups extend
    assert got["requests"] == 24
    assert got["chips"] == 2 * 4              # two batches, four devices


def test_wrapper_handoff_reads_its_own_serve(four_devices):
    got = four_devices["wrapper_handoff"]
    assert got["served"] == 6 * 16
    assert got["wrong"] == []
    assert got["threads"] > 1                 # the serves ran concurrently


def test_one_device_starts_no_thread(four_devices):
    got = four_devices["one_device"]
    assert got["devices"] == [0, 1, 2, 3]     # engines built on four
    assert not got["pool"]
    assert got["threads_added"] == 0
    assert got["caller_only"]


def test_one_device_cluster_serves_in_the_callers_thread():
    """The tier-1 process has one device: no pool, no new thread."""
    cfg = get_config("qwen3-8b").scaled(n_layers=1, vocab_size=VOCAB)
    cluster = SimCluster(n_agents=2, seed=0, max_new_tokens=MAX_NEW,
                         engine_config=cfg)
    before = threading.active_count()
    records, _ = _serve(cluster, 2, 4, True)
    assert all(r is not None and not r.failed for r in records)
    assert cluster._pool is None
    assert threading.active_count() == before


def test_empty_batch_is_no_dispatch():
    """A route batch that matched nothing serves nothing and opens no
    ``dispatch`` span (its mean chips counts served batches only)."""
    from repro.serving.simulator import RoutingProfiler

    cluster = SimCluster(n_agents=2, seed=0, engine_mode="analytic")
    cluster.profiler = prof = RoutingProfiler()
    assert cluster.dispatch([], _Router()) == []
    assert "dispatch" not in prof.calls
    records, _ = _serve(cluster, 1, 3, True)
    assert len(records) == 3 and prof.calls["dispatch"] == 1
    assert prof.counters["dispatch.chips"] == 0     # analytic: no device


def test_failures_follow_decision_order(four_devices):
    got = four_devices["failures"]
    assert got["requests"] == 4 * 12
    assert 0 < got["failed"] < got["requests"]
    assert got["equal"]
    assert got["order"]


def test_at_most_one_worker_a_device(four_devices):
    got = four_devices["workers"]
    assert got["two"]["max_workers"] == 2
    assert 1 <= got["two"]["threads"] <= 2
    assert got["four"]["max_workers"] == 4
    assert 1 <= got["four"]["threads"] <= 4


def test_worker_exception_reaches_the_caller(four_devices):
    got = four_devices["worker_exception"]
    assert got["raised"] == "planted fault"
    assert got["inside_after"] == 0           # every worker had finished
    assert got["hooks_reset"]
    assert got["drawn_left"] == 0


def test_switch_interval_is_short_while_workers_serve(four_devices):
    got = four_devices["switch_interval"]
    assert got["served"] == 8
    assert got["inside"] <= got["limit"] < got["before"]
    assert got["after"] == got["before"]


def test_serve_time_leaves_out_the_host_turn_after_a_wait():
    """Under a wait hook that lets other threads run host code, the engine
    reads its clocks as each wait returns and leaves out how long the host
    took to come back: a hook that keeps it 0.2 s after each of the two
    waits adds nothing to TTFT or to the total."""
    cfg = get_config("qwen3-8b").scaled(n_layers=1, vocab_size=VOCAB)
    cluster = SimCluster(n_agents=1, seed=0, max_new_tokens=MAX_NEW,
                         engine_config=cfg)
    engine = next(iter(cluster.agents.values())).engine
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, VOCAB, 20, dtype=np.int32) for _ in range(2)]
    engine.serve("warm", prompts[0])          # compiles this prompt's shapes

    @contextmanager
    def host_back_late():
        yield
        time.sleep(0.2)

    engine.wait = host_back_late
    t0 = time.perf_counter()
    res = engine.serve("s", prompts[1])
    wall = time.perf_counter() - t0
    assert wall >= 0.4
    assert res.ttft * engine.speed < wall - 0.35
    assert res.total_time * engine.speed < wall - 0.35


def test_event_loop_dispatches_across_devices(four_devices):
    got = four_devices["simulator"]
    assert not got["truncated"]
    assert got["served"] == got["dispatched"] > 0
    assert got["chips"] > got["batches"]      # some batch used several


if __name__ == "__main__":
    print(json.dumps({name: case() for name, case in CASES.items()}))
