"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (set BENCH_QUICK=1 for the
reduced sizes used in CI-style runs).

  table1   Table 1  — KV %, cost, TTFT across 3 workloads x 6 routers
  fig3     Fig. 3   — predictor NMAE (latency / cost / quality)
  fig4     Fig. 4   — cumulative social welfare over turns
  fig5     Fig. 5   — truthful vs strategic bidding utility
  fig6     Fig. 6   — welfare & solver time vs hub count K
  fig7     Fig. 7   — Full-Mix / Ideal / Task-Mix / Agent-Mix economics
  mcmf     §4.3     — Phase-2 solver comparison: mcmf (naive/warm-start VCG)
                      vs dense ε-scaling auction (+ jit variant)
  hubshard §4.4     — hub-sharded Phase 2 at n >= 1k requests: global dense
                      vs per-hub blocks (numpy + vmapped jax buckets),
                      welfare-loss certificate vs the MCMF oracle, and
                      warm- vs cold-started steady-state rounds
  phase1   §4.1     — Phase-1 QoS throughput: scalar per-pair loop vs the
                      batched compiled-forest tensor path (+ jax descend)
  kernels  —        — kernel validation-path timings + batched-LCP speedup
  servingscale §5   — event-driven open-loop serving at 16->128 agents x
                      1k->10k dialogues: per-phase routing overhead as a
                      fraction of simulated engine compute + the >=10%
                      crossover report
  dagrouting   —    — workflow-DAG families (orchestrator fan-out/fan-in,
                      handoff chains): precedence-aware IEMAS vs an
                      affinity-blind graph scheduler on welfare/request,
                      graph makespan and KV hit rate
  adversarial  —    — strategic-agent stress sweep: misreport / collusion /
                      free-rider / churn policies at fleet fractions
                      0-0.5, ground-truth welfare + honest-agent revenue
                      degradation, settlement-ledger replay audit per cell
  fusedrouting —    — fused device-resident routing step vs the staged
                      pipeline at 16->128 agents on one hub: steady-state
                      routing overhead, host-transfer / mid-sync / retrace
                      counters, lockstep decision parity
"""
from __future__ import annotations

import sys
import time

from benchmarks.common import QUICK, start


def main() -> None:
    only = set(sys.argv[1:])
    t0 = time.time()
    print("name,us_per_call,derived")

    def want(name):
        return not only or name in only

    if want("fig5"):
        from benchmarks import fig5_truthfulness
        fig5_truthfulness.run()
    if want("fig6"):
        from benchmarks import fig6_clustering
        fig6_clustering.run()
    if want("fig7"):
        from benchmarks import fig7_schemes
        fig7_schemes.run()
    if want("mcmf"):
        from benchmarks import mcmf_scaling
        mcmf_scaling.run()
    if want("hubshard"):
        from benchmarks import hub_sharding
        hub_sharding.run(smoke=QUICK)
    if want("phase1"):
        from benchmarks import phase1_scaling
        phase1_scaling.run()
    if want("kernels"):
        from benchmarks import kernel_bench
        kernel_bench.run()
    if want("servingscale"):
        from benchmarks import serving_scale
        serving_scale.run(smoke=QUICK)
    if want("dagrouting"):
        from benchmarks import dag_routing
        dag_routing.run(smoke=QUICK)
    if want("adversarial"):
        from benchmarks import adversarial
        adversarial.run(smoke=QUICK)
    if want("fusedrouting"):
        from benchmarks import fused_routing
        fused_routing.run(smoke=QUICK)
    if want("fig3"):
        from benchmarks import fig3_predictor
        fig3_predictor.run()
    if want("fig4"):
        from benchmarks import fig4_welfare
        fig4_welfare.run()
    if want("table1"):
        from benchmarks import table1_efficiency
        table1_efficiency.run()
    print(f"# total_s={time.time() - t0:.0f}", file=sys.stderr)


if __name__ == "__main__":
    start()
    main()
