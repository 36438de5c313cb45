"""Reduce one profiler trace of the measured window to the benchmark's numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it. A device plane (``/device:TPU:<k>``)
holds a line of the operations that ran (``XLA Ops``), each event named by
its HLO instruction (``%auction_bid.1 = (...) custom-call(...)``; the name
is the text before `` = ``), and a line of the programs that ran (``XLA
Modules``, e.g. ``jit_decode_step(<id>)``). Host planes hold the
benchmark's own spans, written as ``jax.profiler.TraceAnnotation``s named
``bench.*``; ``bench.window`` marks the traced window. Everything here is
computed on the trace's own clock.

* device busy time: the union of the operations' intervals inside the
  window, averaged over the chips the cell holds (each device's own union;
  a chip that ran nothing in the window counts as busy 0, whether or not
  the trace has a plane for it);
* device time inside host spans: each device's busy union clipped to spans
  of a name, summed over the devices (device-seconds: work that one chip
  does while the others wait counts once, whichever chip does it);
* kernel events: operations whose instruction name is a kernel's;
* the breakdown: the programs and operations that took most device time,
  and the longest idle gaps labelled by the host span they fall in.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

import numpy as np

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: host spans that label an idle gap, innermost first; a gap outside all of
#: them lies in the loop's own host code
LABELS = (("bench.serve", "serve"), ("bench.route", "routing"),
          ("bench.feedback", "feedback"), ("bench.loop", "loop"))


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def base_name(name: str) -> str:
    """``fusion.12`` -> ``fusion``; ``jit_program(123)`` -> ``jit_program``."""
    name = name.split("(", 1)[0]
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def find_trace(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {directory}")
    return paths[-1]


def merge(intervals) -> np.ndarray:
    """Union of [start, end) intervals as a sorted (k, 2) array."""
    if not len(intervals):
        return np.zeros((0, 2))
    iv = np.asarray(sorted(intervals), np.float64)
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def overlap(union: np.ndarray, spans) -> float:
    """Length of ``union`` (merged) inside the merged ``spans``."""
    spans = merge(spans)
    total, k = 0.0, 0
    for s, e in spans:
        while k < len(union) and union[k, 1] <= s:
            k += 1
        j = k
        while j < len(union) and union[j, 0] < e:
            total += min(e, union[j, 1]) - max(s, union[j, 0])
            j += 1
    return total


class Trace:
    """One traced window (a ``jax.profiler.ProfileData``): device operations
    and bench host spans, in ns. ``chips`` is how many chips the cell holds;
    where the trace shows more devices with operations, those count."""

    def __init__(self, pd, chips: int = 0):
        self.ops: dict = {}           # device plane -> [(s, e, op name)]
        self.modules: dict = {}       # device plane -> [(s, e, program)]
        self.spans = defaultdict(list)  # bench span name -> [(s, e)]
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                for line in plane.lines:
                    if line.name not in (OPS_LINE, MODULES_LINE):
                        continue
                    name = op_name if line.name == OPS_LINE else base_name
                    rows = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                             name(ev.name)) for ev in line.events]
                    if rows:
                        (self.ops if line.name == OPS_LINE else
                         self.modules)[plane.name] = rows
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("bench."):
                            self.spans[ev.name].append(
                                (ev.start_ns, ev.start_ns + ev.duration_ns))
        if not self.spans.get(WINDOW):
            raise ValueError("the trace holds no bench.window span")
        self.t0 = min(s for s, _ in self.spans[WINDOW])
        self.t1 = max(e for _, e in self.spans[WINDOW])
        # each device's busy union inside the window (empty where it ran
        # nothing there)
        self.busy = {dev: merge([(max(s, self.t0), min(e, self.t1))
                                 for s, e, _ in rows
                                 if e > self.t0 and s < self.t1])
                     for dev, rows in self.ops.items()}
        self.chips = max(int(chips), len(self.busy))

    @classmethod
    def load(cls, path: str, chips: int = 0) -> "Trace":
        from jax.profiler import ProfileData

        return cls(ProfileData.from_file(path), chips)

    # ---------------- reductions ----------------
    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_by_device(self) -> dict:
        """Busy seconds in the window of each device the trace shows, by
        plane name."""
        return {dev: float((u[:, 1] - u[:, 0]).sum()) * 1e-9
                for dev, u in self.busy.items()}

    def busy_s(self) -> float:
        """Device busy seconds in the window, averaged over the cell's
        chips."""
        if not self.chips:
            return 0.0
        return sum(self.busy_by_device().values()) / self.chips

    def busy_union(self) -> np.ndarray:
        """The intervals in which any chip ran an operation (merged)."""
        return merge([tuple(iv) for u in self.busy.values() for iv in u])

    def busy_in_sum(self, span: str) -> float:
        """Device-seconds busy inside the host spans named ``span``, summed
        over the devices."""
        spans = self.spans.get(span, [])
        if not spans:
            return 0.0
        return sum(overlap(u, spans) for u in self.busy.values()) * 1e-9

    def kernel_events(self, kernel: str) -> list:
        """(start, end) of every operation of the kernel named ``kernel``
        (instruction ``kernel`` or ``kernel.<n>``), inside the window, over
        all devices."""
        return [(s, e) for rows in self.ops.values() for s, e, name in rows
                if base_name(name) == kernel and e > self.t0 and s < self.t1]

    def _totals(self, table: dict) -> dict:
        tot = defaultdict(float)
        for rows in table.values():
            for s, e, name in rows:
                if e > self.t0 and s < self.t1:
                    tot[base_name(name)] += (min(e, self.t1)
                                             - max(s, self.t0)) * 1e-9
        return tot

    def top_ops(self, k: int = 10) -> list:
        """What took most device time: the programs (``program:<name>``)
        and, within them, the operations by kind (``op:<name>``), seconds
        summed over devices: [[name, s]], at most ``k``."""
        progs = sorted(self._totals(self.modules).items(),
                       key=lambda kv: -kv[1])[: k // 2]
        ops = sorted(self._totals(self.ops).items(), key=lambda kv: -kv[1])
        out = [["program:" + n, v] for n, v in progs]
        out += [["op:" + n, v] for n, v in ops[: k - len(out)]]
        return out

    def idle_gaps(self, k: int = 10) -> list:
        """The longest idle gaps of the busiest device, each labelled by the
        innermost bench host span that covers its middle: [[label, s]]."""
        if not self.busy:
            return [["loop", self.window_s]]
        union = max(self.busy.values(),
                    key=lambda u: (u[:, 1] - u[:, 0]).sum())
        edges = [self.t0] + [x for se in union for x in se] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            mid = 0.5 * (s + e)
            label = "loop"
            for span, name in LABELS:
                if any(a <= mid < b for a, b in self.spans.get(span, ())):
                    label = name
                    break
            out.append([label, (e - s) * 1e-9])
        return out
