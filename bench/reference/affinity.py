"""Plain reference for the router's Phase 1 cache affinity (Eq. 4 of the
IEMAS paper, https://arxiv.org/abs/2603.17302).

The router keeps, per (agent, session), the prompt that agent last served,
and scores a new prompt p_j against agent i by

    o_ij = LCP(p_j, ledger[i, s_j]) / max(1, |p_j|)

where only the ``cache_slots`` sessions that agent i served most recently
count (the agent's published cache summary; older ones score 0). An agent
whose backbone is recurrent can only resume an exact extension of its last
prompt: it scores ``|prev| / |p_j|`` where ``prev`` is a prefix of ``p_j``,
else 0.

`Ledger` replays the window's history from the benchmark's own record:
each completed request's (agent, session, prompt), in the order the
completions reached the router, and each routed market's (sessions,
prompts, agents). It imports nothing of the program.

``check(prog, ref, quant="bf16")`` is the control: the reference's affinity
computed in bfloat16, one precision step below the router's float32.
"""
from __future__ import annotations

from collections import OrderedDict

import numpy as np

import loader


def lcp(a: np.ndarray, b: np.ndarray) -> int:
    n = min(len(a), len(b))
    neq = np.flatnonzero(np.asarray(a[:n]) != np.asarray(b[:n]))
    return int(neq[0]) if len(neq) else n


class Ledger:
    """The last prompt each agent served per session, in recency order."""

    def __init__(self):
        self.rows: dict = {}

    def done(self, agent, session, prompt) -> None:
        od = self.rows.setdefault(agent, OrderedDict())
        od.pop(session, None)
        od[session] = prompt

    def affinity(self, sessions, prompts, agents, slots, recurrent
                 ) -> np.ndarray:
        """(n, m) float64 affinity of each prompt to each agent."""
        o = np.zeros((len(prompts), len(agents)))
        for i, aid in enumerate(agents):
            od = self.rows.get(aid, {})
            keys = list(od)
            recent = set(keys[-int(slots[i]):] if slots[i] > 0 else keys)
            for j, (s, p) in enumerate(zip(sessions, prompts)):
                prev = od.get(s) if s in recent else None
                if prev is None or len(prev) == 0:
                    continue
                k = lcp(p, prev)
                if recurrent[i]:
                    o[j, i] = len(prev) / max(1, len(p)) \
                        if k == len(prev) else 0.0
                else:
                    o[j, i] = k / max(1, len(p))
        return o


def replay(events: list, want: set) -> dict:
    """Affinity matrices of the routed markets whose event index is in
    ``want``. ``events`` holds ``("done", agent, session, prompt)`` and
    ``("route", sessions, prompts, agents, slots, recurrent)`` in order."""
    led, out = Ledger(), {}
    for k, ev in enumerate(events):
        if ev[0] == "done":
            led.done(*ev[1:])
        elif k in want:
            out[k] = led.affinity(*ev[1:])
    return out


def check(prog, ref, quant: str = "none") -> dict:
    """Largest |o_router - o_ref| (or, with ``quant="bf16"``, the control's
    bfloat16 affinity against the exact one)."""
    ref = np.asarray(ref, np.float64)
    if quant == "bf16":
        prog = loader.reference("assignment").bf16(ref)
    elif quant != "none":
        raise ValueError(f"unknown control {quant!r}")
    if ref.size == 0:
        return {"affinity_err": 0.0}
    return {"affinity_err": float(np.max(np.abs(
        np.asarray(prog, np.float64) - ref)))}
