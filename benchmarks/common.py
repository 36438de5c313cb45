"""Shared helpers for the benchmark suite."""
from __future__ import annotations

import os
import time

QUICK = os.environ.get("BENCH_QUICK", "0") == "1"


def start() -> None:
    """Start-up every benchmark entry point runs first (never on import):
    the persistent compile cache (`repro.utils.compile_cache`)."""
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()


def timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, (time.perf_counter() - t0) * 1e6


def emit(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def synthetic_market(n, m, seed=0, domain_structure=True, n_dom=4):
    """Valuations/costs with domain block structure (agents specialize)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    req_dom = rng.integers(0, n_dom, n)
    ag_dom = rng.integers(0, n_dom, m)
    match = (req_dom[:, None] == ag_dom[None, :]).astype(float)
    base_v = rng.uniform(2.0, 6.0, (n, 1))
    values = base_v + (2.0 * match if domain_structure else 0.0) \
        + rng.normal(0, 0.3, (n, m))
    costs = rng.uniform(0.5, 2.5, (1, m)) + rng.normal(0, 0.1, (n, m))
    caps = rng.integers(2, 5, m).tolist()
    return (np.maximum(values, 0), np.maximum(costs, 0.01), caps,
            req_dom, ag_dom)
