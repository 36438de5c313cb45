"""Phase-1 QoS throughput: scalar per-pair loop vs the batched tensor path.

For each (n requests, m agents) the same trained PredictorPool scores the
full Eq.-5 feature tensor three ways:

  * scalar   — the ``batched=False`` oracle: a Python loop building a
               PredictorInput and calling ``AgentPredictor.predict`` per
               (request, agent) pair (three Hoeffding tree walks each);
  * batched  — ``PredictorPool.predict_matrix``: stacked compiled forests,
               one vectorized descend per target, priors/blend as array
               ops. Timed with the compile caches invalidated per call,
               i.e. the realistic serving round where Phase-4 feedback has
               touched every tree since the last batch;
  * jax      — the same with the jit-staged descend (steady state, compile
               excluded; skipped under --smoke / BENCH_QUICK).

Reports pairs/sec and the batched-vs-scalar speedup; the n=16, m=64 row is
the acceptance gate (>= 5x expected; --smoke asserts >= 3x for CI noise)
and the max |batched - scalar| parity error (must be ~0: the batched path
is an oracle-parity optimization, tests/test_predictor_batch.py).

    PYTHONPATH=src:. python benchmarks/phase1_scaling.py [--smoke]
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.common import QUICK, emit, start
from repro.core.predictor import (N_FEATURES, PredictorInput, PredictorPool,
                                  feature_tensor)
from repro.core.pricing import TokenPrices

GATE_SIZE = (16, 64)


def _build_pool(m: int, n_train: int, seed: int = 0) -> PredictorPool:
    rng = np.random.default_rng(seed)
    prices = {f"a{i}": TokenPrices(0.002 * (4 + i % 5), 0.0008, 0.02)
              for i in range(m)}
    pool = PredictorPool(prices, warm_n=6)
    for aid in pool.agents():
        pred = pool[aid]
        base = float(rng.uniform(0.01, 0.05))
        for _ in range(n_train):
            x = rng.uniform(0, 1, N_FEATURES)
            x[0] = rng.uniform(10, 400)          # prompt_len
            uncached = x[0] * (1.0 - x[2])
            pred.update(PredictorInput(*x),
                        base + 1e-3 * uncached + rng.normal(0, 0.002),
                        pred.prices.miss * uncached + rng.normal(0, 0.01),
                        float(rng.random() < 0.6 + 0.3 * x[9]))
    return pool


def _features(n: int, m: int, seed: int = 1) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return feature_tensor(
        rng.uniform(10, 400, n), rng.integers(0, 8, n).astype(float),
        rng.uniform(0, 1, (n, m)),
        router_inflight=float(n), router_rps=2.0,
        agent_inflight=rng.integers(0, 12, m).astype(float),
        agent_rps=rng.uniform(0, 3, m),
        capacity=np.full(m, 12.0),
        domain_match=rng.integers(0, 2, (n, m)).astype(float))


def _invalidate(pool: PredictorPool) -> None:
    """Simulate a feedback round touching EVERY tree since the last batch
    (worst case: a real round touches at most batch-size agents): each tree
    recompiles and is written back into the stacked pool incrementally."""
    for aid in pool.agents():
        for tree in (pool[aid].lat, pool[aid].cost, pool[aid].quality):
            tree._version += 1


def _time(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run(smoke: bool = False):
    smoke = smoke or QUICK
    sizes = [GATE_SIZE] if smoke else \
        [(16, 16), GATE_SIZE, (64, 64), (128, 64), (256, 128)]
    n_train = 40 if smoke else 80
    gate_speedup = None
    for n, m in sizes:
        pool = _build_pool(m, n_train)
        ids = pool.agents()
        X = _features(n, m)
        pairs = n * m

        def scalar():
            out = np.empty((n, m, 3))
            for j in range(n):
                for i, aid in enumerate(ids):
                    est = pool[aid].predict(PredictorInput(*X[j, i]))
                    out[j, i] = est.latency, est.cost, est.quality
            return out

        def batched():
            _invalidate(pool)
            return pool.predict_matrix(ids, X)

        ref = scalar()
        t_scalar = _time(scalar, repeats=1 if pairs > 8192 else 2)
        lat, cst, qual = pool.predict_matrix(ids, X)
        parity = max(np.max(np.abs(ref[..., 0] - lat)),
                     np.max(np.abs(ref[..., 1] - cst)),
                     np.max(np.abs(ref[..., 2] - qual)))
        t_batched = _time(batched, repeats=3)
        speedup = t_scalar / max(t_batched, 1e-12)
        cols = [f"pairs={pairs}",
                f"scalar_pairs_per_s={pairs / t_scalar:.0f}",
                f"batched_pairs_per_s={pairs / t_batched:.0f}",
                f"speedup={speedup:.1f}x",
                f"parity={parity:.2e}"]
        if not smoke:
            pool.predict_matrix(ids, X, backend="jax")  # compile once
            t_jax = _time(lambda: pool.predict_matrix(ids, X, backend="jax"),
                          repeats=3)
            cols.append(f"jax_pairs_per_s={pairs / t_jax:.0f}")
        emit(f"phase1/n{n}_m{m}", t_batched * 1e6, " ".join(cols))
        if (n, m) == GATE_SIZE:
            gate_speedup = speedup
            assert parity <= 1e-12, f"batched path diverged: {parity}"
    if gate_speedup is not None:
        floor = 3.0 if smoke else 5.0
        assert gate_speedup >= floor, (
            f"Phase-1 batched speedup {gate_speedup:.1f}x at "
            f"n{GATE_SIZE[0]}_m{GATE_SIZE[1]} below the {floor}x gate")
        print(f"# gate: {gate_speedup:.1f}x >= {floor}x at "
              f"n{GATE_SIZE[0]}_m{GATE_SIZE[1]} OK")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="gate size only, no jax; CI-friendly")
    smoke = ap.parse_args().smoke
    if not smoke:
        start()
    run(smoke)
