"""Multi-head latent attention (DeepSeek-V2): YaRN rope at the published
constants, and the latent cache through the engine's truncate -> extend ->
decode, which must equal a fresh prefill of the same tokens."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention
from repro.models.layers import (rope_freqs, yarn_correction_range,
                                 yarn_inv_freq)
from repro.serving.engine import AgentEngine

DSV2 = get_config("deepseek-v2-lite-16b")


def test_yarn_frequencies_at_the_published_constants():
    """dim 64, base 10000, factor 40 over 4096 positions, betas 32/1: the
    ramp runs from pair 10 to pair 23; pairs up to 10 keep their frequency,
    pairs from 23 on are divided by 40, the pairs between are mixed."""
    rs = DSV2.rope_scaling
    assert yarn_correction_range(64, 10000.0, rs) == (10, 23)
    base = np.asarray(rope_freqs(64, 10000.0))
    got = np.asarray(yarn_inv_freq(64, 10000.0, rs))
    np.testing.assert_allclose(got[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / 13
    assert got[16] == pytest.approx(base[16] * (1 - ramp + ramp / 40),
                                    rel=1e-6)


def test_mla_softmax_scale_carries_yarn_mscale():
    """(128 + 64)^-0.5 * (1 + 0.1 * 0.707 * ln 40)^2 = 0.11472."""
    scale = attention.mla_softmax_scale(DSV2)
    assert scale == pytest.approx(192 ** -0.5
                                  * (1 + 0.1 * 0.707 * math.log(40)) ** 2)
    assert round(scale, 5) == 0.11472
    plain = dataclasses.replace(DSV2, rope_scaling=None)
    assert attention.mla_softmax_scale(plain) == pytest.approx(192 ** -0.5)


def _scatter_add_extend(ckv, krope, slot_pos, ckv_new, krope_new, pos0,
                        lens_new):
    """A planted fault: the latent write that adds new latents onto what
    the slots held, without clearing them first."""
    m = ckv.shape[1]
    t = jnp.arange(ckv_new.shape[1])
    pos = pos0[:, None] + t[None, :]
    slot = jnp.minimum(pos, m - 1)
    keep = t[None, :] < lens_new[:, None]

    def row_fn(cc, kc, sp, cn, kn, sl, kp, p_row):
        cc = cc.at[sl].add(jnp.where(kp[:, None], cn, 0.0))
        kc = kc.at[sl].add(jnp.where(kp[:, None], kn, 0.0))
        sp = sp.at[sl].max(jnp.where(kp, p_row, -1))
        return cc, kc, sp

    return jax.vmap(row_fn)(ckv, krope, slot_pos, ckv_new, krope_new, slot,
                            keep, pos)


def _truncate_extend_gap(name: str) -> float:
    """Prefill 20 tokens, decode 4, truncate to 20 (the engine's own
    truncation), extend 6, decode 1: the widest gap of the last logits to a
    fresh prefill of the same 27 tokens, over the logits' size."""
    cfg = get_config(name).scaled(dtype="float32")
    eng = AgentEngine(cfg, seed=0, max_len=64)
    params = eng.params
    prefill, decode, extend = (eng._prefill_j, eng._decode_j, eng._extend_j)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (27,), 1,
                                         cfg.vocab_size), np.int32)
    _, cache = prefill(params, {"tokens": jnp.asarray(toks[None, :20])})
    for t in (5, 6, 7, 8):
        _, cache = decode(params, cache, jnp.asarray([t]))
    cache = eng._truncate_attn_cache(cache, 20)
    _, cache = extend(params, cache, jnp.asarray(toks[None, 20:26]),
                      jnp.asarray([6], jnp.int32))
    got, _ = decode(params, cache, jnp.asarray(toks[26:]))
    want, _ = prefill(params, {"tokens": jnp.asarray(toks[None])})
    want = np.asarray(want)
    return float(np.max(np.abs(np.asarray(got) - want))
                 / np.max(np.abs(want)))


@pytest.mark.parametrize("name", ["qwen3-8b", "deepseek-v2-lite-16b"])
def test_truncate_extend_decode_matches_prefill(name):
    """GQA and MLA caches alike: the slots past the kept prefix still hold
    the discarded tokens when the extend writes there. Float32 at reduced
    size; the paths differ only in summation order (2e-4 of the logits'
    size leaves room over the ~1e-6 seen, and far under the stale-latent
    fault's gap of order 1)."""
    assert _truncate_extend_gap(name) < 2e-4


def test_stale_latents_fail_the_truncate_test(monkeypatch):
    from repro.serving import engine as engine_mod

    monkeypatch.setattr(attention, "latent_extend", _scatter_add_extend)
    monkeypatch.setattr(engine_mod, "_SHARED", {})
    assert _truncate_extend_gap("deepseek-v2-lite-16b") > 1e-2
