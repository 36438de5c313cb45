"""Lightweight timing helpers for benchmarks (CPU wall-clock)."""
from __future__ import annotations

import time
from contextlib import nullcontext

import jax


class _NoSpan:
    """The span handle when no profiler is attached: ``set`` does nothing."""

    def set(self, **stats) -> None:
        pass


_NO_SPAN = _NoSpan()


def phase_scope(profiler, name: str, **stats):
    """``profiler.phase(name, **stats)`` or a no-op context when no
    profiler is set; either way the context yields a handle whose
    ``set(**stats)`` attaches end-of-span counters.

    The one shared implementation of the serving-layer profiling idiom:
    routers, the auction layer, the fused step, the engines and the
    serving loops all call this instead of re-deriving the nullcontext
    dispatch (see `repro.serving.simulator.RoutingProfiler`).
    """
    if profiler is None:
        return nullcontext(_NO_SPAN)
    return profiler.phase(name, **stats)


class Timer:
    def __init__(self):
        self.t0 = time.perf_counter()

    def lap(self) -> float:
        t = time.perf_counter()
        dt = t - self.t0
        self.t0 = t
        return dt


def bench_call(fn, *args, warmup: int = 2, iters: int = 5, block: bool = True) -> float:
    """Median wall-clock microseconds per call."""
    for _ in range(warmup):
        out = fn(*args)
        if block:
            jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        if block:
            jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6
