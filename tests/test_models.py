"""Per-arch smoke + decode/extend consistency for the 10 assigned archs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, list_archs, param_counts
from repro.models import build_model

KEY = jax.random.PRNGKey(0)


def _reduced(name):
    return get_config(name).scaled(dtype="float32")


def _batch(red, b, s, key=KEY):
    batch = {"tokens": jax.random.randint(key, (b, s), 0, red.vocab_size)}
    if red.family == "vlm":
        batch["patches"] = jax.random.normal(
            key, (b, red.n_patches, red.d_model)) * 0.1
    if red.is_encdec:
        batch["frames"] = jax.random.normal(
            key, (b, red.src_len, red.d_model)) * 0.1
    return batch


@pytest.mark.parametrize("name", list_archs())
def test_smoke_forward_and_train_step(name):
    """Reduced config: one loss + one grad step, output finite."""
    red = _reduced(name)
    m = build_model(red)
    params = m.init(KEY)
    batch = _batch(red, 2, 24)
    loss, grads = jax.value_and_grad(m.loss)(params, batch)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.sum(g.astype(jnp.float32) ** 2))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("name", list_archs())
def test_decode_matches_parallel(name):
    red = _reduced(name)
    m = build_model(red)
    params = m.init(KEY)
    b, s = 2, 21
    full = _batch(red, b, s + 1)
    pad = red.n_patches if red.family == "vlm" else 0
    ml = s + pad + 4
    pbA = dict(full)
    pbA["tokens"] = full["tokens"][:, :s]
    pbA["max_len"] = ml
    logA, cache = m.prefill(params, pbA)
    logA2, _ = m.decode_step(params, cache, full["tokens"][:, s])
    pbB = dict(full)
    pbB["max_len"] = ml
    logB, _ = m.prefill(params, pbB)
    scale = float(np.max(np.abs(np.asarray(logB)))) + 1e-9
    err = float(np.max(np.abs(np.asarray(logA2) - np.asarray(logB))))
    assert err / scale < 2e-3


@pytest.mark.parametrize("name", [n for n in list_archs()
                                  if n not in ("seamless-m4t-medium",
                                               "zamba2-7b")])
def test_extend_matches_prefill(name):
    red = _reduced(name)
    m = build_model(red)
    params = m.init(KEY)
    b, s, s0 = 2, 21, 13
    full = _batch(red, b, s)
    pad = red.n_patches if red.family == "vlm" else 0
    ml = s + pad + 4
    ref_b = dict(full)
    ref_b["max_len"] = ml
    logRef, _ = m.prefill(params, ref_b)
    pbC = dict(full)
    pbC["tokens"] = full["tokens"][:, :s0]
    pbC["max_len"] = ml
    _, cacheC = m.prefill(params, pbC)
    lens_new = jnp.full((b,), s - s0, jnp.int32)
    logD, _ = m.extend(params, cacheC, full["tokens"][:, s0:], lens_new)
    scale = float(np.max(np.abs(np.asarray(logRef)))) + 1e-9
    err = float(np.max(np.abs(np.asarray(logD) - np.asarray(logRef))))
    assert err / scale < 2e-3


@pytest.mark.parametrize("name", list_archs())
def test_param_counts_analytic_close(name):
    """configs.param_counts tracks real counts within 8% on full configs
    (the rwkv6 formula approximates the ddlerp LoRA stack; 6.6% there)."""
    from repro.utils.tree import param_count

    cfg = get_config(name)
    m = build_model(cfg)
    abstract = jax.eval_shape(m.init, KEY)
    real = param_count(abstract)
    est = param_counts(cfg)["total"]
    assert abs(real - est) / real < 0.08, (real, est)


def test_sliding_window_ring_cache():
    """mixtral-family ring cache: decode equals parallel past the window."""
    red = _reduced("mixtral-8x22b")
    red = dataclasses.replace(red, sliding_window=12)
    m = build_model(red)
    params = m.init(KEY)
    b, s = 1, 40  # several window wraps
    toks = jax.random.randint(KEY, (b, s + 1), 0, red.vocab_size)
    logA, cache = m.prefill(params, {"tokens": toks[:, :s], "max_len": s + 4})
    logA2, _ = m.decode_step(params, cache, toks[:, s])
    logB, _ = m.prefill(params, {"tokens": toks, "max_len": s + 4})
    scale = float(np.max(np.abs(np.asarray(logB)))) + 1e-9
    assert float(np.max(np.abs(np.asarray(logA2) - np.asarray(logB)))) / scale < 2e-3


def test_mla_absorbed_decode_equivalent():
    """DeepSeek-V2 absorbed decode == naive latent-expansion decode."""
    from repro.models import attention as attn_mod

    red = _reduced("deepseek-v2-lite-16b")
    m = build_model(red)
    params = m.init(KEY)
    toks = jax.random.randint(KEY, (2, 13), 0, red.vocab_size)
    _, cache = m.prefill(params, {"tokens": toks, "max_len": 16})
    nxt = jnp.array([3, 5])
    prev = attn_mod.MLA_ABSORBED
    try:
        attn_mod.MLA_ABSORBED = False
        log_naive, _ = m.decode_step(params, cache, nxt)
        attn_mod.MLA_ABSORBED = True
        log_abs, _ = m.decode_step(params, cache, nxt)
    finally:
        attn_mod.MLA_ABSORBED = prev
    a, b = np.asarray(log_naive), np.asarray(log_abs)
    assert np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9) < 2e-3
