"""The benchmark's traffic generator against the program's, the published
shapes of the dialogue mixes, and the arrival schedules' seeding."""
import itertools

import numpy as np
import pytest

import loader

# the program's linear families (repro.serving.workload.iter_dialogues)
PROGRAM = {
    "coqa_like": dict(domain="dialogue", turns=[6, 15], first_tokens=[24, 48],
                      followup_tokens=[6, 14], difficulty=[0.1, 0.5]),
    "quac_like": dict(domain="longctx", turns=[3, 7], first_tokens=[200, 360],
                      followup_tokens=[8, 20], difficulty=[0.3, 0.7]),
}
MIXES = ["coqa_closed16", "coqa_poisson96"]


@pytest.mark.parametrize("stream", sorted(PROGRAM))
def test_generator_equals_program(stream):
    from repro.serving.workload import WorkloadSpec, iter_dialogues

    traffic = dict(PROGRAM[stream], family="multi_turn", stream=stream,
                   dialogues=40)
    gen = loader.family("multi_turn")
    seed, vocab = 2**31 + 9, 151936
    ours = list(gen.dialogues(traffic, seed, vocab))
    theirs = list(iter_dialogues(WorkloadSpec(stream, n_dialogues=40,
                                              vocab=vocab, seed=seed)))
    assert len(ours) == len(theirs) == 40
    for (did, dom, turns, diff), s in zip(ours, theirs):
        assert (did, dom, diff) == (s.dialogue_id, s.domain, s.difficulty)
        assert len(turns) == len(s.turns)
        assert all(np.array_equal(a, b) for a, b in zip(turns, s.turns))


def _blocks(traffic, seed, n_blocks):
    gen = loader.family(traffic["family"])
    b = int(traffic["shuffle_block"])
    ds = list(itertools.islice(gen.dialogues(traffic, seed, 1000),
                               b * n_blocks))
    return [ds[k * b:(k + 1) * b] for k in range(n_blocks)]


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_work(mix):
    """Each block holds the same dialogues' sizes for every seed, in another
    order and with other token ids."""
    traffic = loader.load_json(loader.BENCH / "traffic" / f"{mix}.json")
    a, b = _blocks(traffic, 5, 3), _blocks(traffic, 2**31 + 6, 3)

    def sizes(block):
        return sorted((d[0], tuple(len(t) for t in d[2]), d[3])
                      for d in block)

    assert [sizes(x) for x in a] == [sizes(x) for x in b]
    assert [d[0] for d in a[0]] != [d[0] for d in b[0]]
    by_id = {d[0]: d[2][0] for d in b[0]}
    assert not any(np.array_equal(d[2][0], by_id[d[0]]) for d in a[0])


@pytest.mark.parametrize("mix", MIXES)
def test_coqa_shapes(mix):
    """CoQA's published means (tokens at 1.3 a word), and every dialogue
    fits the engine's 1024 positions with its answers."""
    traffic = loader.load_json(loader.BENCH / "traffic" / f"{mix}.json")
    gen = loader.family(traffic["family"])
    ds = list(gen.dialogues(dict(traffic, dialogues=2000), 3, 1000))
    turns = np.array([len(d[2]) for d in ds])
    passage = np.array([len(d[2][0]) for d in ds])
    follow = np.array([len(t) for d in ds for t in d[2][1:]])
    assert turns.mean() == pytest.approx(15.2, rel=0.05)
    assert passage.mean() == pytest.approx(271 * 1.3 + 5.5 * 1.3, rel=0.05)
    assert follow.mean() == pytest.approx(5.5 * 1.3, rel=0.1)
    out = int(traffic["max_new_tokens"])
    assert out == round(2.7 * 1.3 + 0.5)
    longest = max(sum(len(t) for t in d[2]) + out * len(d[2]) for d in ds)
    assert longest <= 1024


def test_arrivals_follow_the_seed():
    arr = loader.arrivals()
    spec = {"kind": "poisson", "rate": 96.0}
    a = list(itertools.islice(arr.make(spec, 5).times(), 200))
    b = list(itertools.islice(arr.make(spec, 5).times(), 200))
    c = list(itertools.islice(arr.make(spec, 6).times(), 200))
    assert a == b and a != c
    assert all(x < y for x, y in zip(a, a[1:]))
    assert 200 / a[-1] == pytest.approx(96.0, rel=0.25)
    fixed = {"kind": "poisson", "rate": 96.0, "seed": 96}
    assert list(itertools.islice(arr.make(fixed, 5).times(), 50)) == \
        list(itertools.islice(arr.make(fixed, 6).times(), 50))
    closed = list(itertools.islice(arr.make({"kind": "closed"}, 5).times(),
                                   3))
    assert closed == [0.0, 0.0, 0.0]
