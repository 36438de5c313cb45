"""The program's own spans in a traced run, on the device trace's clock.

The serving stack's tracer (`repro.serving.simulator.RoutingProfiler`)
opens a ``jax.profiler.TraceAnnotation`` named ``iemas.<phase>`` around
each phase while a trace is collected, with its counters as the event's
stats (``rounds`` on ``iemas.fused.device``, ``n_hit`` on
``iemas.phase4_feedback``, ...). This reads them back from the traced
run's ``.xplane.pb`` (the file `reduce.find_trace` finds under the
harness's trace directory), loaded once per file, and clips them to
``bench.window``. A program that writes no such spans gives ``None``, so a
metric built on them is left out of the result line.

Device idle time inside spans is the time in which no chip ran an
operation (`reduce.Trace.busy_union`), so host spans and device operations
are compared on one clock. The serving loop dispatches one request at a
time and waits for it, so inside an ``iemas.engine.*`` span only the
serving agent's chip works: the spans need not name the chip.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple

import loader

#: where the harness writes a traced run's profile (``harness.TRACE_DIR``)
TRACE_DIR = loader.ROOT / ".bench_trace"
PREFIX = "iemas."


def _reduce():
    return loader.module(loader.BENCH / "trace" / "reduce.py")


class Span(NamedTuple):
    name: str          # without the ``iemas.`` prefix
    start: float       # ns, on the trace's clock
    end: float
    stats: dict


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime: float) -> tuple:
    """Every ``iemas.*`` host event of one trace file (``mtime`` keys the
    cache to the file's contents)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append(Span(ev.name[len(PREFIX):], ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    dict(ev.stats)))
    return tuple(sorted(out, key=lambda s: (s.start, -s.end)))


def spans(ctx) -> list | None:
    """The program's spans inside the traced window, clipped to it; None
    where the run was not traced or the program wrote no such span."""
    tr = ctx.trace
    if tr is None:
        return None
    try:
        path = _reduce().find_trace(str(TRACE_DIR))
    except FileNotFoundError:
        return None
    out = [Span(s.name, max(s.start, tr.t0), min(s.end, tr.t1), s.stats)
           for s in _load(path, os.path.getmtime(path))
           if s.end > tr.t0 and s.start < tr.t1]
    return out or None


def named(found: list, name: str) -> list:
    return [s for s in found if s.name == name]


def wall_ns(found: list, name: str) -> float:
    return sum(s.end - s.start for s in named(found, name))


def minus(parents: list, children: list) -> list:
    """The parts of the ``parents`` spans that no ``children`` span covers,
    as [(start, end)]."""
    cover = _reduce().merge([(c.start, c.end) for c in children])
    out = []
    for p in parents:
        t = p.start
        for s, e in cover:
            if e <= t or s >= p.end:
                continue
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < p.end:
            out.append((t, p.end))
    return out


def self_ns(found: list, name: str) -> float:
    """Total time of the ``name`` spans outside every other span that lies
    inside one of them (its children and their descendants)."""
    parents = named(found, name)
    inner = [s for s in found if s.name != name and any(
        p.start <= s.start and s.end <= p.end for p in parents)]
    return sum(e - s for s, e in minus(parents, inner))


def idle_ns(ctx, intervals: list) -> float:
    """Time inside ``intervals`` [(start, end)] (merged) in which no chip
    ran an operation."""
    reduce = _reduce()
    union = reduce.merge(intervals)
    total = float((union[:, 1] - union[:, 0]).sum()) if len(union) else 0.0
    return total - reduce.overlap(ctx.trace.busy_union(), intervals)
