"""Dialogue arrival schedules, made from ``--seed``.

``closed``: every dialogue of the stream arrives at t = 0 and the serving
loop's admission window (``max_inflight``) holds the population in flight:
a finished dialogue is replaced by the next one of the stream.
``poisson``: open-loop arrivals at ``rate`` dialogues per virtual second,
with exponential gaps drawn from a generator seeded by the schedule's own
``seed`` where the traffic file fixes one (every run then offers the same
arrivals), else by the run's seed.

Times are virtual seconds of the program's event loop (`EventSimulator`).
"""
from __future__ import annotations

import numpy as np


class Schedule:
    """An arrival-time source the event loop reads through ``times()``."""

    def __init__(self, kind: str, rate: float, seed: int):
        if kind not in ("closed", "poisson"):
            raise ValueError(f"unknown arrival kind {kind!r}")
        if kind == "poisson" and not rate > 0:
            raise ValueError(f"poisson arrivals need a rate > 0, got {rate}")
        self.kind, self.rate, self.seed = kind, float(rate), seed

    def times(self):
        if self.kind == "closed":
            while True:
                yield 0.0
        rng = np.random.default_rng([self.seed, 0x41])
        t = 0.0
        while True:
            t += float(rng.exponential(1.0 / self.rate))
            yield t


def make(spec: dict, seed: int) -> Schedule:
    return Schedule(spec["kind"], spec.get("rate", 0.0),
                    spec.get("seed", seed))
