"""Multi-turn dialogue scripts: the one generator every dialogue mix reads.

With plain ``[lo, hi)`` ranges and no passage this is a copy of the
program's ``repro.serving.workload.iter_dialogues`` for its linear families
(``coqa_like``, ``quac_like``, ``hotpot_like``), with the lengths taken from
the traffic file instead of the code: with the same ``stream`` name and seed
it yields the same scripts, draw for draw (``bench/checks/test_traffic.py``).
The benchmark keeps its own copy so that a change to the program cannot move
the traffic.

Parameters (the traffic file's keys):

    stream           name salted into the seed (the program's family name)
    dialogues        how many scripts the stream holds
    domain           the scripts' domain tag
    turns            user turns per dialogue
    passage_tokens   optional: a context passage that opens the first turn
    first_tokens     tokens of the first user turn (after the passage)
    followup_tokens  tokens of each later user turn
    difficulty       [lo, hi) uniform difficulty (drives simulated quality)
    sizes_seed       optional: draw the lengths, turn counts and difficulties
                     from this fixed seed, and from the run's seed only the
                     token ids and the order of the dialogues within each
                     block of ``shuffle_block`` (default 1): every seed then
                     offers the same work in every block
    shuffle_block    see ``sizes_seed``

A length is ``[lo, hi)`` (uniform integers, the program's draw) or
``{"mean", "cv", "lo", "hi"}``: a gamma draw of that mean and coefficient
of variation, rounded and held to ``[lo, hi)``.

Turn t's prompt is the whole conversation so far: the serving loop appends
each generated answer and the next user turn to the history.
"""
from __future__ import annotations

import zlib

import numpy as np


def dialogues(traffic: dict, seed: int, vocab: int):
    """Yield ``(dialogue_id, domain, turns, difficulty)`` lazily; token ids
    are drawn from ``1 .. vocab - 1``."""
    if "sizes_seed" not in traffic:
        yield from _stream(traffic, seed, vocab)
        return
    ids = np.random.default_rng([seed, 0x70C])
    block = int(traffic.get("shuffle_block", 1))
    sizes = _stream(traffic, traffic["sizes_seed"], vocab)
    while True:
        held = [d for _, d in zip(range(block), sizes)]
        if not held:
            return
        for k in ids.permutation(len(held)):
            did, dom, turns, diff = held[k]
            yield did, dom, [ids.integers(1, vocab, size=len(t),
                                          dtype=np.int32)
                             for t in turns], diff


def _length(rng, spec) -> int:
    if isinstance(spec, dict):
        cv2 = float(spec["cv"]) ** 2
        x = rng.gamma(1.0 / cv2, float(spec["mean"]) * cv2)
        return int(np.clip(round(x), spec["lo"], spec["hi"] - 1))
    return int(rng.integers(spec[0], spec[1]))


def _stream(traffic: dict, seed: int, vocab: int):
    """The program's draw order: one generator for sizes and ids."""
    stream = traffic["stream"]
    rng = np.random.default_rng(seed + zlib.crc32(stream.encode()) % 100000)
    d_lo, d_hi = traffic["difficulty"]
    passage = traffic.get("passage_tokens")
    for d in range(int(traffic["dialogues"])):
        n_turns = _length(rng, traffic["turns"])
        first = _length(rng, traffic["first_tokens"])
        if passage is not None:
            first += _length(rng, passage)
        turns = [rng.integers(1, vocab, size=first, dtype=np.int32)]
        turns += [rng.integers(1, vocab, size=_length(
            rng, traffic["followup_tokens"]), dtype=np.int32)
            for _ in range(n_turns - 1)]
        difficulty = float(rng.uniform(d_lo, d_hi))
        yield f"{stream}-{d}", traffic["domain"], turns, difficulty
