"""Adversarial economic stress sweep: strategic fraction x policy.

For each (policy, fraction) cell a seeded ``AdversaryMix`` turns a fleet
fraction strategic (`repro.core.adversary`), the IEMAS router runs with
reputation-weighted priors and the hash-chained settlement ledger
attached, and a fixed closed-loop workload executes.  Reported per cell,
all from GROUND-TRUTH records (the cluster's measured latency,
cost-at-true-prices and audited quality — never the reports):

  * true welfare  — sum of client_value(audited quality, latency) minus
    true cost over completed requests;
  * honest revenue — settled payments flowing to non-strategic agents;
  * degradation of both vs the fraction-0 baseline.

Every cell must pass ``verify_chain()`` and the replay audit
(balances recomputed from the ledger alone == ``router.accounts``).

Acceptance gates (asserted under ``--smoke``, run in CI):
  * the fraction-0 cell is EXACTLY the honest baseline — zero welfare and
    zero honest-revenue degradation (the audit channel and reputation
    scaling are bit-neutral for honest fleets);
  * the ledger replay audit holds on every cell, including churn.

Run:
    PYTHONPATH=src:. python benchmarks/adversarial.py [--smoke]
"""
from __future__ import annotations

import argparse

from benchmarks.common import QUICK, emit, start
from repro.configs.iemas_cluster import RouterConfig
from repro.core.adversary import POLICIES, AdversaryMix
from repro.core.valuation import client_value
from repro.serving import SimCluster, make_router, run_workload
from repro.serving.workload import WorkloadSpec, generate

FRACTIONS = (0.0, 0.1, 0.25, 0.5)
SMOKE_FRACTIONS = (0.0, 0.25)


def _cell(policy: str | None, fraction: float, *, n_agents: int,
          n_dialogues: int, seed: int) -> dict:
    """One sweep cell: build cluster+router, run the workload, audit the
    ledger, and return ground-truth welfare / honest-revenue metrics."""
    mix = None
    if policy is not None:
        mix = AdversaryMix(policy=policy, fraction=fraction, seed=seed + 7)
    cluster = SimCluster(n_agents, seed=seed, engine_mode="analytic",
                         adversary_mix=mix)
    router = make_router(cluster, RouterConfig(
        solver="dense", n_hubs=2, warm_start=True, audit_ledger=True))
    spec = WorkloadSpec("coqa_like", n_dialogues=n_dialogues, seed=seed + 1)
    run_workload(cluster, router, generate(spec), max_new_tokens=4)
    adv = set(cluster.adversaries)
    welfare = sum(
        float(client_value(r.quality, r.latency, router.valuation)) - r.cost
        for r in cluster.records)
    honest_rev = sum(r.payment for r in cluster.records
                     if r.agent_id not in adv)
    balances = router.settlement.audit(router.accounts)  # raises on mismatch
    reps = router.pool.reputations()
    return {
        "welfare": welfare,
        "honest_rev": honest_rev,
        "n": len(cluster.records),
        "n_adversaries": len(adv),
        "settled": balances["settled"],
        "faults": balances["faults"],
        "rep_min": min(reps.values()) if reps else 1.0,
        "matched": router.accounts["matched"],
        "unmatched": router.accounts["unmatched"],
    }


def run(smoke: bool = False):
    """Full sweep (or the reduced CI smoke): emit one CSV row per cell and
    assert the fraction-0 / ledger gates under ``smoke``."""
    quick = smoke or QUICK
    n_agents = 8 if quick else 12
    n_dialogues = 10 if quick else 32
    seed = 0
    fractions = SMOKE_FRACTIONS if quick else FRACTIONS
    base = _cell(None, 0.0, n_agents=n_agents, n_dialogues=n_dialogues,
                 seed=seed)
    emit("adversarial/baseline/f0.00", 0.0,
         f"welfare={base['welfare']:.4f} honest_rev={base['honest_rev']:.4f} "
         f"n={base['n']} settled={base['settled']} ledger_ok=True")
    out = {None: {0.0: base}}
    for policy in POLICIES:
        rows = out.setdefault(policy, {})
        for frac in fractions:
            cell = _cell(policy, frac, n_agents=n_agents,
                         n_dialogues=n_dialogues, seed=seed)
            rows[frac] = cell
            d_w = base["welfare"] - cell["welfare"]
            d_r = base["honest_rev"] - cell["honest_rev"]
            emit(f"adversarial/{policy}/f{frac:.2f}", 0.0,
                 f"welfare={cell['welfare']:.4f} "
                 f"honest_rev={cell['honest_rev']:.4f} "
                 f"dwelfare={d_w:.4f} dhonest_rev={d_r:.4f} "
                 f"adv={cell['n_adversaries']} settled={cell['settled']} "
                 f"faults={cell['faults']} rep_min={cell['rep_min']:.3f} "
                 f"ledger_ok=True")
            if smoke and frac == 0.0:
                # bit-neutrality gate: a zero-fraction mix IS the honest
                # baseline — any drift means the audit channel, reputation
                # scaling or ledger perturbed an honest run
                assert cell["welfare"] == base["welfare"], \
                    f"{policy}: welfare degradation at fraction 0: " \
                    f"{cell['welfare']} != {base['welfare']}"
                assert cell["honest_rev"] == base["honest_rev"], \
                    f"{policy}: honest-revenue drift at fraction 0"
                assert cell["n_adversaries"] == 0
        # honest-revenue degradation curve (monotone for the theft-style
        # policies in the full sweep; reported, not asserted — small smoke
        # populations are noisy)
        degr = [base["honest_rev"] - rows[f]["honest_rev"]
                for f in fractions]
        mono = all(a <= b + 1e-9 for a, b in zip(degr, degr[1:]))
        emit(f"adversarial/{policy}/degradation", 0.0,
             " ".join(f"f{f:.2f}={d:.4f}" for f, d in zip(fractions, degr))
             + f" monotone={mono}")
    return out


def main():
    """CLI entry point."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes + fraction-0/ledger gates (CI)")
    args = ap.parse_args()
    run(smoke=args.smoke)


if __name__ == "__main__":
    start()
    main()
