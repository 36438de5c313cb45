"""The engine's greedy decode loop as one device program (`_greedy`):
the same tokens and final cache as a per-step loop of the engine's own
decode step, one compile for every output length up to eight tokens, and
stored sessions left as they were by a later serve that starts from them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.serving.engine import AgentEngine

#: a dense attention model and a recurrent one (rwkv6), tiny, float32
FAMILIES = {
    "dense": get_config("qwen3-8b").scaled(dtype="float32", vocab_size=64),
    "rwkv6": get_config("rwkv6-3b").scaled(dtype="float32", vocab_size=64),
}


def _prompt(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(1, 60, n).astype(np.int32)


def _host(tree):
    # copies: a host view of a device buffer would keep it from donation
    return jax.tree.map(np.array, tree)


def _assert_same(a, b):
    jax.tree.map(np.testing.assert_array_equal, _host(a), _host(b))


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def engine(request):
    return AgentEngine(FAMILIES[request.param], seed=0, max_len=64)


@pytest.mark.parametrize("max_new", [1, 3, 8, 9])
def test_generate_matches_per_step_loop(engine, max_new):
    prompt = _prompt(max_new, 21)
    engine.drop_session("s")
    res = engine.serve("s", prompt, max_new_tokens=max_new)
    # the oracle: the first logits, then the per-step decode + argmax
    logits, cache, _ = engine._first_logits(prompt, None, "fresh", 0)
    want = []
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    for _ in range(max_new):
        want.append(int(tok[0]))
        logits, cache = engine._decode_j(engine.params, cache, tok)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    np.testing.assert_array_equal(res.output_tokens, want)
    assert res.n_gen == max_new
    stored = engine.sessions["s"]
    np.testing.assert_array_equal(stored.prompt,
                                  np.concatenate([prompt, want]))
    _assert_same(stored.cache, cache)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_generate_compiles_once_up_to_eight_tokens(family):
    # a max_len of its own: a model class whose programs no other test ran
    eng = AgentEngine(FAMILIES[family], seed=1, max_len=48)
    prompt = _prompt(7, 9)
    for max_new in range(1, 9):
        # fresh, then extend past the stored session, then cached whole
        eng.drop_session("s")
        eng.serve("s", prompt, max_new_tokens=max_new)
        eng.serve("s", np.concatenate([eng.sessions["s"].prompt, [5, 6]]),
                  max_new_tokens=max_new)
        eng.serve("s", eng.sessions["s"].prompt, max_new_tokens=max_new)
    assert eng._generate_j._cache_size() == 1


def test_later_serves_leave_stored_sessions_unchanged(engine):
    engine.sessions.clear()
    engine.serve("parent", _prompt(3, 17), max_new_tokens=4)
    parent = engine.sessions["parent"]
    before = _host(parent.cache)
    # the whole prompt cached: each decode loop starts from the parent's
    # stored cache, forked by a handoff, then in its own session
    engine.serve("child", parent.prompt, max_new_tokens=4,
                 parents=("parent",))
    assert engine.sessions["parent"] is parent
    engine.serve("parent", parent.prompt, max_new_tokens=4)
    _assert_same(parent.cache, before)
    # a handoff fork that extends the parent's context
    parent = engine.sessions["parent"]
    before = _host(parent.cache)
    res = engine.serve("fork", np.concatenate([parent.prompt, _prompt(4, 5)]),
                       max_new_tokens=4, parents=("parent",))
    assert res.n_hit == len(parent.prompt)
    _assert_same(parent.cache, before)
