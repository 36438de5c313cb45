"""Fig. 3: online QoS predictor accuracy — NMAE of latency/cost/quality
estimates vs observations over multi-turn interactions (paper: 0.101 / 0.090
/ 0.069)."""
from __future__ import annotations

import numpy as np

from benchmarks.common import QUICK, emit, start
from repro.core import IEMASRouter
from repro.core.pricing import observed_cost
from repro.serving import SimCluster, WorkloadSpec, generate, run_workload


def run():
    cluster = SimCluster(n_agents=4, seed=2, max_new_tokens=4, warmup=True)
    router = IEMASRouter(cluster.agent_infos(), predictor_kw={"warm_n": 4})
    errs = {"latency": [], "cost": [], "quality": []}
    preds = {}

    orig = router.on_complete

    def tracked(request_id, obs):
        entry = router._pending.get(request_id)
        if entry is not None and not obs.failed:
            x, agent, req, payment, pc = entry
            est = router.pool[agent.agent_id].predict(x)
            cost = observed_cost(agent.prices, obs.n_prompt, obs.n_hit, obs.n_gen)
            errs["latency"].append((est.latency, obs.latency))
            errs["cost"].append((est.cost, cost))
            errs["quality"].append((est.quality, obs.quality))
        return orig(request_id, obs)

    router.on_complete = tracked
    n_dialogues = 8 if QUICK else 16
    dialogues = generate(WorkloadSpec("coqa_like", n_dialogues=n_dialogues,
                                      seed=3))
    run_workload(cluster, router, dialogues, max_rounds=3000)

    out = {}
    for key, pairs in errs.items():
        arr = np.array(pairs[len(pairs) // 3:])  # post-warm-up regime
        pred, obs = arr[:, 0], arr[:, 1]
        scale = max(obs.mean(), 1e-9) if key != "quality" else 1.0
        out[key] = float(np.mean(np.abs(pred - obs)) / scale)
    emit("fig3/nmae", 0.0,
         f"latency={out['latency']:.3f} cost={out['cost']:.3f} "
         f"quality={out['quality']:.3f} (paper: 0.101/0.090/0.069)")
    return out


if __name__ == "__main__":
    start()
    run()
