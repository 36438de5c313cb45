"""A reduced DeepSeek-V2 (latent attention with YaRN rope, a dense layer,
then dropless MoE layers) served by `AgentEngine` for three turns of one
dialogue, in float32, against the benchmark's plain reference forward
(``bench/reference/deepseek_v2.py``, built from the same Hugging Face keys
through ``bench/adapters/deepseek_v2.py``).

Turn 2's prompt departs from what turn 1 generated, so the engine truncates
its latent cache to the common prefix and extends over the discarded
tokens' slots; turn 3 extends the cache as it stands. Each planted fault
(no YaRN mscale^2 in the softmax scale, a renormalised gate, the latent
write that adds onto stale slots) must fail the comparison."""
import dataclasses
import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention
from repro.serving import engine as engine_mod
from repro.serving.engine import AgentEngine

BENCH = Path(__file__).resolve().parents[1] / "bench"

#: DeepSeek-V2-Lite's keys at a small size (the published rope scaling,
#: gate and expert counts per token kept)
TINY = {
    "hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32,
    "q_lora_rank": None, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
    "v_head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 8, "n_shared_experts": 2, "num_experts_per_tok": 3,
    "norm_topk_prob": False, "routed_scaling_factor": 1.0,
    "scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
    "moe_layer_freq": 1, "vocab_size": 512, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "rope_scaling": {
        "type": "yarn", "factor": 40, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
        "mscale_all_dim": 0.707},
    "attention_bias": False, "tie_word_embeddings": False,
    "hidden_act": "silu", "torch_dtype": "float32",
}
#: the widest gap of a turn's first-token logits to the reference's, and
#: of a generated token's reference logit below the reference's best.
#: Both are float32; the program reorders the sums (absorbed decode,
#: grouped experts), which moves logits of size ~3 by ~1e-6: 1e-3 leaves
#: that a wide margin and is far below what each fault moves (1e-1 and up)
TOL = 1e-3


def _bench(kind: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _bench("reference", "deepseek_v2")
ADAPTER = _bench("adapters", "deepseek_v2")


def _no_mscale(cfg):
    return (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5


def _stale_latents(ckv, krope, slot_pos, ckv_new, krope_new, pos0, lens_new):
    m = ckv.shape[1]
    t = jnp.arange(ckv_new.shape[1])
    pos = pos0[:, None] + t[None, :]
    keep = t[None, :] < lens_new[:, None]

    def row_fn(cc, kc, sp, cn, kn, sl, kp, p_row):
        cc = cc.at[sl].add(jnp.where(kp[:, None], cn, 0.0))
        kc = kc.at[sl].add(jnp.where(kp[:, None], kn, 0.0))
        return cc, kc, sp.at[sl].max(jnp.where(kp, p_row, -1))

    return jax.vmap(row_fn)(ckv, krope, slot_pos, ckv_new, krope_new,
                            jnp.minimum(pos, m - 1), keep, pos)


def _serve(cfg):
    """Three turns of one dialogue: each turn's (prompt, generated tokens),
    and each turn's first-token logits."""
    eng = AgentEngine(cfg, seed=0, max_len=128, max_new_tokens=4)
    eng.params = REF.make_params(TINY, 5, 0)
    seen = []
    generate = eng._generate_j

    def spy(params, cache, logits, n, size):
        seen.append(np.asarray(logits[0]))
        return generate(params, cache, logits, n, size=size)

    eng._generate_j = spy
    rng = np.random.default_rng(2)
    words = lambda n: rng.integers(1, 512, n).astype(np.int32)  # noqa: E731
    turns, prompt = [], words(30)
    for turn in range(3):
        res = eng.serve("d", prompt)
        turns.append((prompt, res.output_tokens))
        if turn == 0:
            # an answer other than the generated one: truncate to 30
            answer = (res.output_tokens + 1) % 511 + 1
            prompt = np.concatenate([prompt, answer, words(7)])
        else:
            prompt = np.concatenate([prompt, res.output_tokens, words(6)])
    assert eng.sessions["d"].prompt.size == len(turns[-1][0]) + 4
    return turns, seen


def _gaps(turns, seen) -> tuple:
    params = REF.make_params(TINY, 5, 0)
    seqs = [np.concatenate([p, o[:-1]]) for p, o in turns]
    reads = [np.arange(len(p) - 1, len(p) - 1 + len(o)) for p, o in turns]
    ref = REF.reference_logits(params, TINY, seqs, reads)
    first = max(float(np.max(np.abs(lg[0] - got)))
                for lg, got in zip(ref, seen))
    served = max(float(np.max(lg.max(1) - lg[np.arange(len(o)), o]))
                 for lg, (_, o) in zip(ref, turns))
    return first, served


@pytest.fixture
def fresh_programs(monkeypatch):
    monkeypatch.setattr(engine_mod, "_SHARED", {})


def test_three_turns_match_the_reference(fresh_programs):
    first, served = _gaps(*_serve(ADAPTER.program_config(TINY)))
    assert first < TOL and served < TOL


@pytest.mark.parametrize("fault", ["no_mscale", "renormalised_gate",
                                   "stale_latents"])
def test_planted_fault_fails(fault, fresh_programs, monkeypatch):
    cfg = ADAPTER.program_config(TINY)
    if fault == "no_mscale":
        monkeypatch.setattr(attention, "mla_softmax_scale", _no_mscale)
    elif fault == "renormalised_gate":
        cfg = dataclasses.replace(cfg, norm_topk_prob=True)
    else:
        monkeypatch.setattr(attention, "latent_extend", _stale_latents)
    first, served = _gaps(*_serve(cfg))
    assert max(first, served) > 100 * TOL


def test_reference_yarn_agrees_with_the_program():
    """The reference's own YaRN (written from the published formulas) and
    the program's, at the published rope constants."""
    from repro.models.layers import yarn_inv_freq

    cfg = ADAPTER.program_config(dict(TINY, qk_rope_head_dim=64))
    inv, cs, scale = REF.yarn(dict(TINY, qk_rope_head_dim=64,
                                   qk_nope_head_dim=128))
    np.testing.assert_allclose(
        inv, np.asarray(yarn_inv_freq(64, 10000.0, cfg.rope_scaling)),
        rtol=1e-6)
    assert cs == 1.0
    assert scale == pytest.approx(
        192 ** -0.5 * (1 + 0.1 * 0.707 * math.log(40)) ** 2)
