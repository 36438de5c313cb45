"""The DeepSeek-V2 family's reference (`reference/deepseek_v2.py`) against
the program's forward pass, its fp8 control separated from the program, its
counts against hand counts at the cell's widths, and the adapter's refusal
of published settings the program does not implement."""
from types import SimpleNamespace

import numpy as np
import pytest

import loader

#: DeepSeek-V2-Lite's keys at a small size (rope scaling, gate and experts
#: per token as published)
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
CELL = loader.load_json(loader.BENCH / "configs" / "deepseek-v2-lite-x2.json")


def _model(config):
    from repro.models import build_model

    return build_model(loader.adapter("deepseek_v2").program_config(config))


def test_reference_matches_program_forward():
    """At float32 the program's prefill, decode and extend agree with the
    reference's full forward pass over the same tokens and weights."""
    import jax.numpy as jnp

    ref = loader.reference("deepseek_v2")
    model = _model(TINY)
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
    pad = np.zeros(8, np.int32)
    pad[:5] = ext
    elog, _ = model.extend(params, cache, jnp.asarray(pad[None]),
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

    ref = loader.reference("deepseek_v2")
    cfg = dict(TINY, torch_dtype="bfloat16", vocab_size=4096)
    model = _model(cfg)
    params = ref.make_params(cfg, 11, 0)
    pairs = []
    for s in range(6):
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


def test_params_take_the_programs_layout():
    import jax

    ref = loader.reference("deepseek_v2")
    model = _model(TINY)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: ref.make_params(TINY, 3, 1))
    shape = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)  # noqa
    assert shape(got) == shape(want)


# ---- counts, by hand at the cell's widths (D 2048, 16 heads, latent 512,
# rope 64, no-rope 128, values 128, dense 10944, 64 experts of 1408, top 6,
# 2 shared, vocab 102400; 5 layers: 1 dense + 4 MoE; bfloat16)
_ATTN_W = (2048 * 16 * 192      # wq
           + 2048 * 576         # kv_a: latent + rope key
           + 512 * 16 * 128     # kv_b, keys
           + 512 * 16 * 128     # kv_b, values
           + 16 * 128 * 2048    # wo
           + 512 + 2 * 2048)    # latent norm, two block norms
_DENSE_W = 3 * 2048 * 10944
_EXPERT_W = 3 * 2048 * 1408
_MOE_W = 2048 * 64 + (6 + 2) * _EXPERT_W        # gate, 6 routed, 2 shared
_HEAD_W = 2048 * 102400 + 2048                  # LM head, final norm


def test_decode_call_by_hand():
    ref = loader.reference("deepseek_v2")
    ops, nbytes = ref.call_counts(CELL, 1, 400)
    weights = 5 * _ATTN_W + _DENSE_W + 4 * _MOE_W + _HEAD_W
    latents = 5 * 576 * 2 * 401                 # 400 read, 1 written
    assert nbytes == 2 * weights + 2 * 2048 + latents
    # one token: projections, attention over 401 positions in the absorbed
    # form (latent scores 512 + rope 64, latent mixing 512 per head and
    # position; kv_b folded into the query and the output), the head
    attn = (2048 * 16 * 192 + 2048 * 576 + 16 * 128 * 2048
            + 512 * 16 * 256 + 401 * 16 * (512 + 64 + 512))
    ffn = _DENSE_W + 4 * _MOE_W
    assert ops == 2 * (5 * attn + ffn + 2048 * 102400)


def test_prefill_call_by_hand():
    ref = loader.reference("deepseek_v2")
    ops, nbytes = ref.call_counts(CELL, 352, 0)
    pairs = 352 * 353 // 2
    # expanded form: kv_b on each of the 352 latents, then per head and
    # (query, key) pair 192 score and 128 mixing multiply-adds
    attn = (352 * (2048 * 16 * 192 + 2048 * 576 + 16 * 128 * 2048
                   + 512 * 16 * 256) + pairs * 16 * (192 + 128))
    ffn = 352 * (_DENSE_W + 4 * _MOE_W)
    assert ops == 2 * (5 * attn + ffn + 2048 * 102400)
    weights = 5 * _ATTN_W + _DENSE_W + 4 * _MOE_W + _HEAD_W
    assert nbytes == 2 * weights + 352 * 2 * 2048 + 5 * 576 * 2 * 352


def test_expert_ffn_by_hand():
    ref = loader.reference("deepseek_v2")
    # a decode step's MoE layer: 6 rows through 6 experts
    assert ref.expert_ffn_counts(CELL, 6, 6) == (
        2 * 6 * _EXPERT_W, 2 * (6 * _EXPERT_W + 2 * 6 * 2048))
    # an extend's: 60 rows over 40 experts
    assert ref.expert_ffn_counts(CELL, 60, 40) == (
        2 * 60 * _EXPERT_W, 2 * (40 * _EXPERT_W + 2 * 60 * 2048))


def test_cell_holds_the_published_widths():
    """2.84e9 parameters an agent (5.68 GB in bfloat16)."""
    import jax

    ref = loader.reference("deepseek_v2")
    shapes = jax.eval_shape(lambda: ref.make_params(CELL, 1, 0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    # _MOE_W counts 6 routed and 2 shared experts: 58 more are held
    assert n == 5 * _ATTN_W + _DENSE_W + 4 * (_MOE_W + 58 * _EXPERT_W) \
        + _HEAD_W + 2048 * 102400
    assert 2.83e9 < n < 2.85e9


@pytest.mark.parametrize("key, value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("topk_method", "group_limited"),
    ("scoring_func", "sigmoid")])
def test_adapter_refuses_what_the_program_lacks(key, value):
    adapter = loader.adapter("deepseek_v2")
    with pytest.raises(ValueError, match="does not implement"):
        adapter.program_config(dict(TINY, **{key: value}))


def test_tiny_cell_through_the_harness():
    """The family's cell at a small size on the CPU, traced: the window's
    served requests are correct, the float8 control is not, and every
    engine span of the MoE model carries its expert counters (a decode
    step's rows are ``top_k`` a layer)."""
    import time

    import harness

    fix = loader.BENCH / "checks" / "fixtures"
    config = loader.load_json(fix / "tiny-dsv2.json")
    traffic = loader.load_json(fix / "tiny-coqa.json")
    res = harness.run_cell(loader.benchmark(), {"name": "tiny", "chips": 1},
                           config, traffic, 2**31 + 77, 2.0, True,
                           time.perf_counter(), require_chip=False,
                           controls={"engine": ("fp8",)})
    assert res["correct"], res["checks"]
    assert not res["control_checks"]["fp8"]["correct"]
    program = loader.module(loader.BENCH / "trace" / "program.py")
    reduce = loader.module(loader.BENCH / "trace" / "reduce.py")
    tr = reduce.Trace.load(reduce.find_trace(str(program.TRACE_DIR)))
    found = program.spans(SimpleNamespace(trace=tr))
    decode = program.named(found, "engine.decode")
    first = [s for s in found if s.name in ("engine.prefill",
                                            "engine.extend")]
    assert decode and first
    for s in decode:
        assert s.stats["moe_rows"] == s.stats["steps"] * 2 * 3
        assert 3 <= s.stats["moe_experts"] <= s.stats["moe_rows"]
    for s in first:
        assert s.stats["moe_rows"] % 3 == 0 and s.stats["moe_experts"] > 0
