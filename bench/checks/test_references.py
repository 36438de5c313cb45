"""The plain references: the exact allocation and Clarke payments against
the program's exact oracle, the dense reference against the program's own
forward pass, and each control separated from the program."""
import numpy as np
import pytest

import loader


@pytest.mark.parametrize("shape", [(16, 2), (40, 9), (64, 24)])
def test_assignment_matches_program_oracle(shape):
    from repro.core.solvers.mcmf import McmfBackend

    ref = loader.reference("assignment")
    rng = np.random.default_rng(sum(shape))
    n, m = shape
    values = rng.uniform(0, 2, (n, m))
    costs = rng.uniform(0, 1, (n, m))
    caps = rng.integers(1, 6, m).tolist()
    truth = ref.clarke(values, costs, caps)
    prog = McmfBackend().solve(np.maximum(values - costs, 0), costs, caps)
    assert truth["welfare"] == pytest.approx(prog.welfare, rel=1e-12)
    got = ref.check_market(values, costs, caps, prog.assignment,
                           prog.payments)
    assert got == {"over_capacity": 0, "welfare_gap": pytest.approx(0, abs=1e-12),
                   "payment_err": pytest.approx(0, abs=1e-9)}


def test_assignment_catches_faults_and_control():
    ref = loader.reference("assignment")
    rng = np.random.default_rng(3)
    values, costs = rng.uniform(0, 2, (32, 4)), rng.uniform(0, 1, (32, 4))
    caps = [6, 6, 6, 6]
    truth = ref.clarke(values, costs, caps)
    alloc = list(truth["alloc"])
    pay = ref._payments(truth, alloc)
    limits = loader.load_json(loader.BENCH / "configs" / "fleet128.json")[
        "check"]["limits"]
    bad_pay = pay.copy()
    j = next(j for j, i in enumerate(alloc) if i >= 0)
    bad_pay[j] *= 1.01
    assert ref.check_market(values, costs, caps, alloc, bad_pay)[
        "payment_err"] > limits["route.payment_err"]
    moved = list(alloc)
    moved[j] = (alloc[j] + 1) % 4
    got = ref.check_market(values, costs, caps, moved, pay)
    assert got["welfare_gap"] > limits["route.welfare_gap"] or \
        got["over_capacity"] > 0
    ctl = ref.check_market(values, costs, caps, alloc, pay, quant="bf16")
    assert ctl["payment_err"] > limits["route.payment_err"]


TINY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
        "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
        "torch_dtype": "float32", "attention_bias": False,
        "tie_word_embeddings": False}


def test_dense_reference_matches_program_forward():
    """At float32 the program's prefill, extend and decode agree with the
    reference's full forward pass over the same tokens and weights."""
    import jax.numpy as jnp

    from repro.models import build_model

    ref = loader.reference("dense_lm")
    model = build_model(loader.adapter("dense_lm").program_config(TINY))
    params = ref.make_params(TINY, 7, 0)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 512, 21).astype(np.int32)
    logits, cache = model.prefill(params, {
        "tokens": jnp.asarray(prompt[None]),
        "lens": jnp.asarray([21], jnp.int32), "max_len": 64})
    toks, got = [], [np.asarray(logits[0])]
    for _ in range(4):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        toks.append(int(tok[0]))
        logits, cache = model.decode_step(params, cache, tok)
        got.append(np.asarray(logits[0]))
    ext = rng.integers(1, 512, 5).astype(np.int32)
    elog, _ = model.extend(params, cache, jnp.asarray(ext[None]),
                           jnp.asarray([5], jnp.int32))
    got.append(np.asarray(elog[0]))
    seq = np.concatenate([prompt, toks, ext])
    reads = list(range(20, 25)) + [len(seq) - 1]
    want = ref.reference_logits(params, TINY, [seq], [reads])[0]
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=2e-4)


def test_engine_control_separates():
    """Served greedy tokens of the bfloat16 program at a small size: the
    float8 control's widest gap is well above the program's."""
    import jax.numpy as jnp

    from repro.models import build_model

    ref = loader.reference("dense_lm")
    cfg = dict(TINY, torch_dtype="bfloat16", vocab_size=4096)
    model = build_model(loader.adapter("dense_lm").program_config(cfg))
    pairs = []
    for s in range(6):
        params = ref.make_params(cfg, 11, 0)
        prompt = np.random.default_rng(s).integers(1, 4096, 40).astype(
            np.int32)
        logits, cache = model.prefill(params, {
            "tokens": jnp.asarray(prompt[None]),
            "lens": jnp.asarray([40], jnp.int32), "max_len": 64})
        out = []
        for _ in range(6):
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            out.append(int(tok[0]))
            logits, cache = model.decode_step(params, cache, tok)
        pairs.append((prompt, np.asarray(out, np.int32)))
    gaps = ref.served_gaps(cfg, 11, {0: pairs}, controls=("fp8",))
    assert gaps["tokens"] == 36
    assert gaps["fp8"] > 3 * gaps["program"]
