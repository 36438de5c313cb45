"""SimCluster's real engines: the one-model override (``engine_config``) and
round-robin device placement (`agent_device`)."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import IEMASRouter
from repro.serving import SimCluster, WorkloadSpec, generate, run_workload
from repro.serving.cluster import agent_device


@pytest.fixture(scope="module")
def tiny_cfg():
    """qwen3-8b's family (GQA, qk-norm, bf16) at a CPU-sized width."""
    return get_config("qwen3-8b").scaled(n_layers=1, vocab_size=300)


def test_round_robin_over_four_devices():
    devices = ["dev0", "dev1", "dev2", "dev3"]
    got = [agent_device(i, devices) for i in range(10)]
    assert got == [devices[i % 4] for i in range(10)]


def test_one_device_holds_every_agent():
    devices = jax.devices()
    assert len(devices) == 1
    assert {agent_device(i, devices) for i in range(7)} == {devices[0]}


def test_engine_config_override_serves_on_the_placed_device(tiny_cfg):
    cluster = SimCluster(n_agents=3, seed=0, max_new_tokens=2,
                         engine_config=tiny_cfg)
    for rt in cluster.agents.values():
        assert rt.engine.cfg is tiny_cfg
        assert rt.engine.device == jax.devices()[0]
        assert {d for leaf in jax.tree.leaves(rt.engine.params)
                for d in leaf.devices()} == {jax.devices()[0]}
    router = IEMASRouter(cluster.agent_infos())
    dialogues = generate(WorkloadSpec("coqa_like", n_dialogues=2, seed=3,
                                      vocab=tiny_cfg.vocab_size))
    out = run_workload(cluster, router, dialogues, max_new_tokens=2)
    assert out["n"] == sum(len(d.turns) for d in dialogues)
    assert out["kv_hit_rate"] > 0.0
    # generated ids span the override's vocabulary, not the toy 256
    top = max(int(r.output_tokens.max()) for r in cluster.records)
    assert 0 <= top < tiny_cfg.vocab_size


def test_default_engines_keep_their_model_classes():
    cluster = SimCluster(n_agents=3, seed=0, max_new_tokens=2)
    names = sorted(rt.engine.cfg.name for rt in cluster.agents.values())
    assert names == ["engine-llama3-7b", "engine-qwen-4b", "engine-qwen-8b"]
    assert all(rt.engine.cfg.vocab_size == 256
               for rt in cluster.agents.values())


def test_engine_config_is_refused_for_analytic_engines(tiny_cfg):
    with pytest.raises(ValueError, match="engine_config"):
        SimCluster(n_agents=2, engine_mode="analytic", engine_config=tiny_cfg)


def test_generated_ids_above_255_thread_through_the_ledger(tiny_cfg):
    """Token ids from a wide vocabulary survive the prefix ledger and the
    analytic engine's cache accounting unchanged."""
    from repro.core.affinity import PrefixLedger
    from repro.serving.analytic import AnalyticEngine

    toks = np.array([1, 151935, 70000, 256, 300], np.int32)
    ledger = PrefixLedger()
    ledger.update("a", "d", toks)
    assert np.array_equal(ledger.get("a", "d"), toks)
    assert ledger.affinity("a", "d", np.concatenate([toks, [9]])) == 5 / 6
    eng = AnalyticEngine("qwen-4b", max_new_tokens=2)
    eng.serve("d", toks)
    r = eng.serve("d", np.concatenate([eng.sessions["d"].prompt, [151000]]))
    assert r.n_hit == len(toks) + 2
