"""Operations and bytes that the measured work needs, from its shapes.

Kept with the benchmark (beside ``peaks.json``) so that every change is
judged on the same work: these count what the algorithm needs, not what a
particular implementation pads or recomputes. A model family's forward call
is counted in its own reference file (``call_counts``), beside its forward
pass.
"""
from __future__ import annotations

import loader

F32 = 4


def auction_bid(n: int, m: int) -> tuple:
    """One forward-bidding round over an n x m agent-level market:
    (operations, bytes). Reads W (n x m float32), ask and ask2 (m each) and
    the active mask (n); writes best and winner (m each) and wants (n).
    About 8 operations per market entry: the profit, the top-2 scan (max,
    argmax, masked re-scan, max), the bid and the segment max. The bound is
    the bytes: a bidding round is a streaming pass over W."""
    ops = 8 * n * m
    nbytes = F32 * (n * m + 2 * m + n) + F32 * (2 * m + n)
    return ops, nbytes


def served_request(model: dict, n_prompt: int, n_hit: int,
                   n_gen: int) -> tuple:
    """The engine calls one served request makes: a prefill of the prompt
    (no cached tokens) or an extend of its uncached suffix, then one decode
    step per generated token, each counted by the ``call_counts`` of the
    configuration's model family (``reference/<engine.family>.py``).
    Returns (operations, bytes)."""
    call = loader.reference(model["engine"]["family"]).call_counts
    ops = nbytes = 0
    new = n_prompt - n_hit
    if new > 0:
        o, b = call(model, new, n_hit)
        ops, nbytes = ops + o, nbytes + b
    for k in range(n_gen):
        o, b = call(model, 1, n_prompt + k)
        ops, nbytes = ops + o, nbytes + b
    return ops, nbytes


def fused_route(n: int, m: int, prompt_tokens: int, rounds: int) -> int:
    """Operations one fused routing step needs: the Eq.-4 prefix match
    (compare and running product for every prompt token against each of the
    m agents' ledger rows) and ``rounds`` bidding rounds over n x m."""
    return 2 * prompt_tokens * m + rounds * auction_bid(n, m)[0]
