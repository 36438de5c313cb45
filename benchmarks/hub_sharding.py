"""Hub-sharded Phase-2 auctions: multi-hub welfare loss vs wall-clock speedup.

The ISSUE-3 tentpole measurement (paper §4.4 / Fig. 6 at serving scale):
at n >= 1k requests per batch, carving the (requests x agents) welfare
matrix into K per-hub blocks and auctioning each block independently must
buy a large wall-clock win over the single global dense auction at a small,
certified welfare loss.  Reports, per size:

  * global    — one dense ε-scaling auction + batched Clarke payments over
                the full matrix (the PR-1 hot path);
  * sharded   — `run_sharded_auction` over K domain-clustered hub blocks
                (same solver per block; per-block payments);
  * shard-jax — the same blocks padded into power-of-two shape buckets and
                solved by ONE vmapped jax program per bucket (steady state,
                compile excluded); shard-pallas is the identical batch path
                with the Pallas bidding kernel swapped in;
  * spill     — the cross-hub second round under domain-PINNED routing (no
                per-batch capacity balancing, i.e. the router's real coarse
                classifier): welfare fraction without/with the spill
                re-auction plus rescued/candidate counts — the ROADMAP's
                K=4 small-n welfare-loss tail and its fix;
  * warm      — a steady-state re-auction (next batch from the same
                distribution) seeded from the previous round's slot prices,
                vs the identical re-auction cold: rounds + wall-clock;
  * welfare   — sharded welfare as a fraction of global.  The global dense
                welfare is itself certified within `gap_bound` (= 2·n·ε,
                ~1e-7 relative) of the exact MCMF optimum, so
                `loss_vs_mcmf <= (1 - welfare_frac) + gap_bound/W` — the
                reported `loss_bound` column.  Under `--oracle` (default at
                the smallest size) the exact MCMF also runs directly.

Acceptance gate (checked when the n >= 1000 row runs; `--smoke` runs the
reduced sizes and asserts splice parity + warm <= cold rounds + the spill
round rescuing welfare under pinned routing instead): sharded >= 3x faster
than global with loss_bound <= 2%, and warm-started rounds strictly below
cold rounds on the steady-state batch.

    PYTHONPATH=src:. python benchmarks/hub_sharding.py [--smoke] [--oracle]
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.common import QUICK, emit, start, synthetic_market
from repro.core.auction import SPILL_HUB, run_auction, run_sharded_auction
from repro.core.hub import cluster_agents


def _route(n, k, hubs, caps, req_dom, ag_dom, capacity_spill=True):
    """Coarse stage: every request lands in exactly one hub (domain overlap
    with capacity spill — the fig6 classifier at benchmark scale).

    ``capacity_spill=False`` routes by domain overlap alone — the router's
    actual coarse classifier, which has no per-batch capacity balancing and
    therefore overloads popular hubs (the cross-hub spill study's regime).
    """
    remaining = [sum(caps[i] for i in hub.agent_indices) for hub in hubs]
    hub_of_req = []
    for j in range(n):
        scores = []
        for h, hub in enumerate(hubs):
            match = sum(1 for i in hub.agent_indices
                        if ag_dom[i] == req_dom[j])
            penalty = -10.0 if capacity_spill and remaining[h] <= 0 else 0.0
            scores.append((match / max(len(hub.agent_indices), 1)
                           + penalty, h))
        h = max(scores)[1]
        hub_of_req.append(h)
        remaining[h] -= 1
    return hub_of_req


def _blocks(values, k, caps, req_dom, ag_dom, capacity_spill=True):
    n, m = values.shape
    agent_domains = [(f"dom{d}",) for d in ag_dom]
    hubs = cluster_agents(agent_domains, [1.0] * m, k, scheme="domain")
    hub_of_req = _route(n, k, hubs, caps, req_dom, ag_dom, capacity_spill)
    blocks = {}
    for h, hub in enumerate(hubs):
        r_idx = [j for j in range(n) if hub_of_req[j] == h]
        if r_idx and hub.agent_indices:
            blocks[h] = (r_idx, list(hub.agent_indices))
    return blocks


def _time(fn, repeats):
    best, out = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return out, best * 1e6


def _welfare(results):
    return sum(r.welfare for r in results.values())


def run(smoke: bool = False, oracle: bool | None = None):
    quick = smoke or QUICK
    sizes = [(192, 48, 4)] if quick else [(256, 64, 4), (1000, 128, 8),
                                          (2000, 128, 8)]
    repeats = 1 if quick else 2
    for row, (n, m, k) in enumerate(sizes):
        values, costs, caps, req_dom, ag_dom = synthetic_market(
            n, m, seed=29, n_dom=k)
        blocks = _blocks(values, k, caps, req_dom, ag_dom)

        r_global, t_global = _time(
            lambda: run_auction(values, costs, caps, solver="dense"), repeats)
        sharded, t_shard = _time(
            lambda: run_sharded_auction(values, costs, caps, blocks,
                                        solver="dense"), repeats)
        run_sharded_auction(values, costs, caps, blocks,
                            solver="dense-jax")          # compile once
        _, t_jax = _time(
            lambda: run_sharded_auction(values, costs, caps, blocks,
                                        solver="dense-jax"), repeats)
        run_sharded_auction(values, costs, caps, blocks,
                            solver="pallas")             # compile once
        _, t_pallas = _time(
            lambda: run_sharded_auction(values, costs, caps, blocks,
                                        solver="pallas"), repeats)

        w_global, w_shard = r_global.welfare, _welfare(sharded)
        frac = w_shard / max(w_global, 1e-12)
        gap = r_global.solver_stats["gap_bound"]
        loss_bound = (1.0 - frac) + gap / max(w_global, 1e-12)
        speedup = t_global / max(t_shard, 1.0)

        # steady state: the serving loop re-auctions a statistically
        # overlapping batch; warm-start seeds each hub from this round's
        # final duals, cold re-solves from scratch
        rng = np.random.default_rng(31)
        v2 = np.maximum(values + rng.normal(0, 0.1, values.shape), 0.0)
        seeds = {h: np.concatenate([np.asarray(p) for p in
                                    sharded[h].solver_stats["agent_prices"]])
                 for h in sharded}
        cold2, t_cold2 = _time(
            lambda: run_sharded_auction(v2, costs, caps, blocks,
                                        solver="dense"), repeats)
        warm2, t_warm2 = _time(
            lambda: run_sharded_auction(v2, costs, caps, blocks,
                                        solver="dense", start_prices=seeds),
            repeats)
        rounds_cold = sum(r.solver_stats["rounds"] for r in cold2.values())
        rounds_warm = sum(r.solver_stats["rounds"] for r in warm2.values())
        w_gap2 = abs(_welfare(warm2) - _welfare(cold2)) / max(_welfare(cold2),
                                                              1e-12)

        # cross-hub spill study: domain-PINNED routing (the router's real
        # coarse classifier balances nothing per batch) overloads popular
        # hubs while others keep slack; spill=True re-auctions the losers
        # over the residual capacity and splices the rescues in
        pblocks = _blocks(values, k, caps, req_dom, ag_dom,
                          capacity_spill=False)
        pin, _ = _time(lambda: run_sharded_auction(
            values, costs, caps, pblocks, solver="dense"), 1)
        # spill_agents widens the residual market to hubs pinned routing
        # sent nothing (their capacity is 100% idle), like the router does;
        # the spill round is warm-seeded from the donor hubs' duals by
        # default — the cold run quantifies what the seed saves
        pin_spill_cold, t_spill_cold = _time(lambda: run_sharded_auction(
            values, costs, caps, pblocks, solver="dense", spill=True,
            spill_agents=list(range(m)), spill_warm=False), 1)
        pin_spill, t_spill = _time(lambda: run_sharded_auction(
            values, costs, caps, pblocks, solver="dense", spill=True,
            spill_agents=list(range(m))), 1)
        w_pin, w_pin_spill = _welfare(pin), _welfare(pin_spill)
        sp = pin_spill.get(SPILL_HUB)
        spill_stats = sp.solver_stats["spill"] if sp is not None else \
            {"rescued": 0, "candidates": 0}
        sp_cold = pin_spill_cold.get(SPILL_HUB)
        spill_rounds_warm = sp.solver_stats["rounds"] if sp is not None else 0
        spill_rounds_cold = (sp_cold.solver_stats["rounds"]
                             if sp_cold is not None else 0)

        cols = [f"global_us={t_global:.0f}", f"shard_us={t_shard:.0f}",
                f"shard_jax_us={t_jax:.0f}", f"shard_pallas_us={t_pallas:.0f}",
                f"speedup={speedup:.1f}x",
                f"welfare_frac={frac:.4f}", f"loss_bound={loss_bound:.4f}",
                f"warm_rounds={rounds_warm}", f"cold_rounds={rounds_cold}",
                f"warm_us={t_warm2:.0f}", f"cold_us={t_cold2:.0f}",
                f"warm_welfare_gap={w_gap2:.1e}",
                f"pin_frac={w_pin / max(w_global, 1e-12):.4f}",
                f"pin_spill_frac={w_pin_spill / max(w_global, 1e-12):.4f}",
                f"spill_rescued={spill_stats['rescued']}"
                f"/{spill_stats['candidates']}",
                f"pin_spill_us={t_spill:.0f}",
                f"pin_spill_cold_us={t_spill_cold:.0f}",
                f"spill_rounds={spill_rounds_warm}w/{spill_rounds_cold}c"]

        want_oracle = oracle if oracle is not None else (row == 0)
        if want_oracle and n <= 512:
            r_mcmf, t_mcmf = _time(
                lambda: run_auction(values, costs, caps, solver="mcmf"), 1)
            cols += [f"mcmf_us={t_mcmf:.0f}",
                     f"loss_vs_mcmf={1.0 - w_shard / r_mcmf.welfare:.4f}"]

        emit(f"hubshard/n{n}_m{m}_k{k}", t_shard, " ".join(cols))

        if smoke:
            # correctness gates (size-independent); perf gates need n >= 1k
            assert frac > 0.9, f"sharded welfare fraction {frac}"
            assert w_gap2 < 1e-6, f"warm/cold welfare gap {w_gap2}"
            assert rounds_warm < rounds_cold, \
                f"warm rounds {rounds_warm} >= cold {rounds_cold}"
            # spill gates: pinned routing strands welfare, the cross-hub
            # round recovers some of it without touching first-round results
            assert spill_stats["rescued"] > 0, "spill rescued nothing"
            assert w_pin_spill > w_pin, \
                f"spill welfare {w_pin_spill} <= pinned {w_pin}"
            # donor-dual seeding: warm-spill rounds never exceed cold's,
            # and the rescue welfare matches within certificates
            assert spill_rounds_warm <= spill_rounds_cold, \
                f"warm spill rounds {spill_rounds_warm} > " \
                f"cold {spill_rounds_cold}"
            if sp is not None and sp_cold is not None:
                gap = (sp.solver_stats["gap_bound"]
                       + sp_cold.solver_stats["gap_bound"] + 1e-9)
                assert abs(sp.welfare - sp_cold.welfare) <= gap
            for h in pin:
                assert pin_spill[h].assignment == pin[h].assignment, \
                    f"hub {h}: spill round altered a first-round result"
            # splice parity: every sharded block bit-equals a solo solve
            for h, (r_idx, a_idx) in blocks.items():
                solo = run_auction(values[np.ix_(r_idx, a_idx)],
                                   costs[np.ix_(r_idx, a_idx)],
                                   [caps[i] for i in a_idx], solver="dense")
                assert sharded[h].assignment == solo.assignment, \
                    f"hub {h}: sharded assignment != solo"
                assert sharded[h].payments == solo.payments, \
                    f"hub {h}: sharded payments != solo"
        elif n >= 1000:
            assert speedup >= 3.0, f"hub sharding speedup {speedup:.1f}x < 3x"
            assert loss_bound <= 0.02, f"welfare loss bound {loss_bound:.4f}"
            assert rounds_warm < rounds_cold, \
                f"warm rounds {rounds_warm} >= cold {rounds_cold}"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced size + correctness gates (CI)")
    ap.add_argument("--oracle", action="store_true",
                    help="also run the exact MCMF oracle on every row <= 512")
    args = ap.parse_args()
    run(smoke=args.smoke, oracle=args.oracle or None)


if __name__ == "__main__":
    start()
    main()
