"""Hubs-of-hubs federation: oracle parity, exactly-once, determinism.

The load-bearing contracts:

* S=1 `FederatedSimulator` is a bit-exact oracle for `EventSimulator` —
  same decisions, same accounts, same settlement-ledger head — in both
  the closed-loop lockstep regime and the open-loop Poisson regime.
* S>1 runs settle every dialogue exactly once under faults AND forced
  cross-super-hub migration (hash-chained per-shard ledgers + disjoint
  request-id prefixes + migration conservation).
* Results are bit-deterministic under ANY shard-advance schedule (the
  fold_in-style per-shard seed split shares no mutable state).
* Gossip staleness consumed by spill is bounded by one epoch when
  digests refresh every boundary.
* A process-parallel run is bit-identical to the inline run (same
  `InlineShard.from_spec` factory on both sides of the pipe).
"""
import numpy as np
import pytest

from repro.core import IEMASRouter
from repro.core.hub import cluster_super_hubs, route_to_super_hub
from repro.serving import (EventSimulator, SimCluster, SyncArrivals,
                           build_federation)
from repro.serving.workload import PoissonArrivals, WorkloadSpec, generate

ROUTER_KW = dict(solver="dense", warm_start=True, audit_ledger=True)


def _sig(cluster):
    """Bit-comparable per-record signature, in completion order."""
    return [(r.request.request_id, r.request.dialogue_id, r.request.turn,
             r.agent_id, r.n_prompt, r.n_hit, r.payment, r.latency,
             r.dispatched_at) for r in cluster.records]


def _single_heap(dlg, *, fail=0.0, **loop_kw):
    cluster = SimCluster(n_agents=4, seed=0, max_new_tokens=3,
                         engine_mode="analytic", fail_prob=fail)
    router = IEMASRouter(cluster.agent_infos(), n_hubs=2, **ROUTER_KW)
    out = EventSimulator(cluster, router, dlg, max_new_tokens=3,
                         **loop_kw).run()
    return cluster, router, out


def _federated_s1(dlg, *, fail=0.0, **loop_kw):
    fed = build_federation(
        dlg, n_agents=4, super_hubs=1,
        arrivals=loop_kw.pop("arrivals", None), seed=0,
        router_kwargs=dict(ROUTER_KW, n_hubs=2),
        loop_kwargs=dict(loop_kw, max_new_tokens=3),
        cluster_kwargs=dict(max_new_tokens=3, fail_prob=fail))
    out = fed.run()
    return fed.shards[0].cluster, fed.shards[0].router, out


# ---------------------------------------------------- S=1 oracle parity --
@pytest.mark.parametrize("fail", [0.0, 0.2])
def test_s1_bit_parity_lockstep(fail):
    """S=1 federation reproduces EventSimulator bit-for-bit in the
    quantized closed-loop regime — decisions, accounts, ledger head —
    including the fault path (same rng draw order)."""
    dlg = generate(WorkloadSpec("coqa_like", n_dialogues=7, seed=3))
    c1, r1, m1 = _single_heap(dlg, fail=fail, arrivals=SyncArrivals(),
                              batch_cap=4, quantize=0.05)
    c2, r2, m2 = _federated_s1(dlg, fail=fail, arrivals=SyncArrivals(),
                               batch_cap=4, quantize=0.05)
    assert _sig(c1) == _sig(c2)
    assert r1.accounts == r2.accounts
    assert r1.settlement.head == r2.settlement.head
    assert m1["n"] == m2["n"]
    assert m2["federation"]["exactly_once"]["ok"]


def test_s1_bit_parity_open_loop():
    """Same oracle contract under Poisson arrivals and a bounded
    admission window — the streaming regime, epoch pauses included."""
    dlg = generate(WorkloadSpec("coqa_like", n_dialogues=20, seed=5))
    c1, r1, m1 = _single_heap(
        dlg, arrivals=PoissonArrivals(rate=12.0, seed=2), batch_cap=8,
        batch_window=0.05, max_inflight=16)
    c2, r2, m2 = _federated_s1(
        dlg, arrivals=PoissonArrivals(rate=12.0, seed=2), batch_cap=8,
        batch_window=0.05, max_inflight=16)
    assert _sig(c1) == _sig(c2)
    assert r1.accounts == r2.accounts
    assert r1.settlement.head == r2.settlement.head


# -------------------------------------------- exactly-once + migration --
def _overloaded_federation(*, fail=0.0, shard_schedule=None, seed=0,
                           rate=300.0, parallel="inline"):
    """3 super-hubs with every dialogue forced into ONE domain: the home
    shard saturates, the other two idle — spill must migrate."""
    dlg = generate(WorkloadSpec("coqa_like", n_dialogues=150, seed=1))
    dom = sorted({d.domain for d in dlg})[0]
    dlg = [type(d)(d.dialogue_id, dom, d.turns, d.difficulty) for d in dlg]
    return build_federation(
        dlg, n_agents=12, super_hubs=3,
        arrivals=PoissonArrivals(rate=rate, seed=2), seed=seed,
        router_kwargs=dict(ROUTER_KW),
        loop_kwargs=dict(batch_cap=32, batch_window=0.05, max_new_tokens=4),
        cluster_kwargs=dict(max_new_tokens=4, fail_prob=fail),
        max_inflight=900, epoch=0.25, spill_min_wait=0.2,
        shard_schedule=shard_schedule, parallel=parallel)


def test_s3_exactly_once_under_faults_and_migration():
    """Every dialogue settles exactly once when the saturated shard spills
    across super-hubs AND agents fault mid-flight: per-shard ledger
    replays verify, request-id prefixes stay disjoint, migration hand-offs
    conserve dialogues, and nothing is lost or double-completed."""
    out = _overloaded_federation(fail=0.1).run()
    eo = out["federation"]["exactly_once"]
    assert out["federation"]["spill_migrated"] > 0   # migration exercised
    assert out["migrated_in"] == out["migrated_out"] > 0
    assert eo["ok"] and eo["ledger_replay_ok"]
    assert eo["lost_dialogues"] == 0
    assert eo["ledgers_attached"] == 3
    assert out["dialogues_arrived"] == 150
    assert out["dialogues_completed"] + out["unfinished_dialogues"] == 150
    assert not out["truncated"]


def test_spill_rescues_saturated_shard():
    """The spill round moves work onto idle remote capacity: migrated
    dialogues complete remotely (the destination shard books completions
    it never admitted as arrivals)."""
    dlg = generate(WorkloadSpec("coqa_like", n_dialogues=400, seed=1))
    dom = sorted({d.domain for d in dlg})[0]
    dlg = [type(d)(d.dialogue_id, dom, d.turns, d.difficulty) for d in dlg]
    out = build_federation(
        dlg, n_agents=12, super_hubs=3,
        arrivals=PoissonArrivals(rate=400.0, seed=2), seed=0,
        router_kwargs=dict(ROUTER_KW),
        loop_kwargs=dict(batch_cap=32, batch_window=0.05, max_new_tokens=4),
        cluster_kwargs=dict(max_new_tokens=4),
        max_inflight=1200, epoch=0.25, spill_min_wait=0.2).run()
    assert out["federation"]["spill_candidates"] > 0
    assert out["federation"]["spill_migrated"] > 0
    receivers = [s for s in out["shards"] if s["migrated_in"] > 0]
    assert receivers and all(s["n"] > 0 for s in receivers)
    assert out["dialogues_completed"] == 400


# ------------------------------------------------------- determinism --
def test_bit_determinism_under_shuffled_shard_schedule():
    """Shard advance order is irrelevant: the fold_in-style seed split
    gives every shard its own rng stream, so reversed / rotating epoch
    schedules replay identical ledger heads and accounts."""
    base = _overloaded_federation().run()

    def rotating(epoch_idx):
        order = [0, 1, 2]
        k = epoch_idx % 3
        return order[k:] + order[:k]

    for sched in ([2, 1, 0], rotating):
        out = _overloaded_federation(shard_schedule=sched).run()
        assert [s["ledger"]["head"] for s in out["shards"]] == \
            [s["ledger"]["head"] for s in base["shards"]]
        assert out["accounts"] == base["accounts"]
        assert out["federation"]["spill_migrated"] == \
            base["federation"]["spill_migrated"]


def test_shard_seed_split_is_stable_and_decorrelated():
    """`shard_seed` is a pure function of (base, super_id) with distinct
    outputs across shards — never scheduling-dependent."""
    from repro.distributed.federation import shard_seed
    seeds = [shard_seed(7, k) for k in range(16)]
    assert seeds == [shard_seed(7, k) for k in range(16)]  # reproducible
    assert len(set(seeds)) == 16                           # decorrelated
    assert shard_seed(8, 0) != shard_seed(7, 0)


# ------------------------------------------------------------ gossip --
def test_gossip_staleness_bounded_by_one_epoch():
    """With digests refreshed at every boundary, no spill valuation ever
    consumes a digest older than one epoch."""
    fed = _overloaded_federation()
    out = fed.run()
    g = out["federation"]["gossip"]
    assert g["digests"] == 3
    assert g["max_staleness_epochs"] <= 1


# ----------------------------------------------------- process workers --
def test_process_parallel_bit_identical_to_inline():
    """An S=2 run with each shard in its own OS process replays the
    inline run bit-for-bit (same `InlineShard.from_spec` on both sides)."""
    def run(parallel):
        dlg = generate(WorkloadSpec("coqa_like", n_dialogues=40, seed=1))
        fed = build_federation(
            dlg, n_agents=16, super_hubs=2,
            arrivals=PoissonArrivals(rate=30.0, seed=2), seed=0,
            router_kwargs=dict(ROUTER_KW),
            loop_kwargs=dict(batch_cap=16, batch_window=0.05,
                             max_new_tokens=4),
            cluster_kwargs=dict(max_new_tokens=4),
            max_inflight=128, epoch=0.25, parallel=parallel)
        out = fed.run()
        return out, [s["ledger"]["head"] for s in out["shards"]]

    o1, h1 = run("inline")
    o2, h2 = run("process")
    assert h1 == h2
    assert o1["accounts"] == o2["accounts"]
    assert o2["federation"]["exactly_once"]["ok"]


@pytest.mark.parametrize("engine_mode,solver", [
    ("real", "dense"), ("analytic", "dense-jax"), ("analytic", "pallas")])
def test_process_mode_refuses_device_work(engine_mode, solver):
    """Workers are CPU-only: real engines and device solvers need the chip
    the parent holds, so process mode refuses them before spawning."""
    dlg = generate(WorkloadSpec("coqa_like", n_dialogues=2, seed=1))
    with pytest.raises(ValueError, match="CPU-only"):
        build_federation(dlg, n_agents=4, super_hubs=2, seed=0,
                         engine_mode=engine_mode,
                         router_kwargs=dict(solver=solver),
                         parallel="process")


@pytest.mark.parametrize("argv", [
    ["--solver", "pallas"], ["--solver", "dense-jax"],
    ["--engine-mode", "real"]])
def test_serve_cli_refuses_device_work_in_process_mode(argv, monkeypatch):
    from repro.launch import serve

    monkeypatch.setattr("sys.argv", [
        "serve", "--sim-mode", "event", "--super-hubs", "2",
        "--federation-parallel", "process", *argv])
    with pytest.raises(SystemExit) as err:
        serve.main()
    assert err.value.code == 2


@pytest.mark.parametrize("parent", ["tpu", None])
def test_workers_spawn_with_cpu_only_jax(parent, monkeypatch):
    """The environment a worker is spawned with pins JAX to the CPU, and
    the parent's own setting is restored afterwards."""
    import os

    from repro.distributed.federation import _cpu_only_children

    if parent is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", parent)
    with _cpu_only_children():
        assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert os.environ.get("JAX_PLATFORMS") == parent


# ------------------------------------------------------- partitioning --
def test_cluster_super_hubs_positional_ids_and_coverage():
    """Super-hub ids are list positions (shard seeds / rid prefixes key on
    them) and the partition covers every agent exactly once."""
    rng = np.random.default_rng(0)
    doms = [("qa",), ("code",), ("math",), ("qa", "code")] * 8
    scales = list(rng.uniform(0.5, 2.0, len(doms)))
    supers = cluster_super_hubs(doms, scales, 3)
    assert [sh.hub_id for sh in supers] == list(range(len(supers)))
    seen = sorted(i for sh in supers for i in sh.agent_indices)
    assert seen == list(range(len(doms)))
    for d in ("qa", "code", "math"):
        k = route_to_super_hub(d, supers, doms)
        assert 0 <= k < len(supers)
