"""The dropless MoE layer (`models/moe.py`) and its grouped expert kernel
(`kernels/moe_gmm.py`, interpret mode here) against per-token loops: every
(token, expert) pair the gate picks is computed, padded positions send no
row to any expert, and the gate renormalises its top-k weights only where
the config says so."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.ref import moe_gmm_ref
from repro.models import moe

KEY = jax.random.PRNGKey(3)


def _cfg(norm_topk_prob=False, **kw):
    """A reduced DeepSeek-V2-Lite MoE layer (8 experts, top 3, 2 shared)."""
    return dataclasses.replace(
        get_config("deepseek-v2-lite-16b").scaled(dtype="float32"),
        n_experts=8, top_k=3, moe_d_ff=32, n_shared_experts=2,
        norm_topk_prob=norm_topk_prob, routed_scaling_factor=1.5, **kw)


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def _loop(p, x, cfg):
    """Per-token oracle: softmax gate, top-k, each picked expert's SwiGLU
    weighted by its gate weight, plus the shared experts."""
    x = np.asarray(x, np.float64)
    out = np.zeros_like(x)
    for t in range(len(x)):
        logits = x[t] @ np.asarray(p["router"], np.float64)
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(-probs, kind="stable")[: cfg.top_k]
        w = probs[top]
        if cfg.norm_topk_prob:
            w = w / w.sum()
        w = w * cfg.routed_scaling_factor
        for e, we in zip(top, w):
            out[t] += we * np.asarray(_swiglu(
                x[t], *(np.asarray(p[n][e], np.float64)
                        for n in ("wg", "wu", "wd"))))
        sp = p["shared"]
        out[t] += np.asarray(_swiglu(x[t], *(np.asarray(sp[n], np.float64)
                                             for n in ("wg", "wu", "wd"))))
    return out


def _layer(cfg, n, key=KEY):
    kp, kx = jax.random.split(key)
    p = moe.moe_init(kp, cfg, jnp.float32)
    x = jax.random.normal(kx, (n, cfg.d_model), jnp.float32)
    return p, x


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_dropless_moe_equals_per_token_loop(norm_topk_prob):
    cfg = _cfg(norm_topk_prob)
    p, x = _layer(cfg, 50)
    out, stats = moe.moe_ffn(p, x.reshape(2, 25, -1), cfg)
    np.testing.assert_allclose(np.asarray(out).reshape(50, -1),
                               _loop(p, x, cfg), rtol=1e-4, atol=1e-5)
    _, experts = moe.route(p, x, cfg)
    assert stats.tolist() == [50 * cfg.top_k,
                              len(np.unique(np.asarray(experts)))]


def test_gate_renormalises_only_where_configured():
    p, x = _layer(_cfg(), 7)
    w_raw, e_raw = moe.route(p, x, _cfg(False))
    w_norm, e_norm = moe.route(p, x, _cfg(True))
    np.testing.assert_array_equal(e_raw, e_norm)
    np.testing.assert_allclose(np.asarray(w_norm).sum(-1), 1.5, rtol=1e-6)
    assert np.all(np.asarray(w_raw).sum(-1) < 1.5 * 0.999)


def test_every_token_to_one_expert():
    """A gate that puts expert 5 first for every token: its group of 150
    rows straddles two row tiles of the kernel, and is computed whole."""
    cfg = _cfg()
    p, x = _layer(cfg, 150)
    # a constant input column that only expert 5's router reads
    p["router"] = p["router"].at[:, 5].set(0.0).at[1, 5].set(10.0)
    x = x.at[:, 1].set(4.0)
    _, experts = moe.route(p, x, cfg)
    assert np.all(np.asarray(experts)[:, 0] == 5)
    out, stats = moe.moe_ffn(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out), _loop(p, x, cfg),
                               rtol=1e-4, atol=1e-5)
    assert int(stats[0]) == 150 * cfg.top_k


def test_padded_positions_send_no_rows():
    """Positions past a row's length route nowhere: the valid positions'
    outputs do not depend on the padding, and only their rows count."""
    cfg = _cfg()
    p, x = _layer(cfg, 24)
    x = x.reshape(2, 12, -1)
    valid = jnp.arange(12)[None, :] < jnp.array([[5], [9]])
    out, stats = moe.moe_ffn(p, x, cfg, valid)
    noisy = jnp.where(valid[..., None], x, 100.0 * x)
    out2, stats2 = moe.moe_ffn(p, noisy, cfg, valid)
    v = np.asarray(valid)
    np.testing.assert_array_equal(np.asarray(out)[v], np.asarray(out2)[v])
    want = _loop(p, np.asarray(x)[v], cfg)
    np.testing.assert_allclose(np.asarray(out)[v], want, rtol=1e-4,
                               atol=1e-5)
    _, experts = moe.route(p, np.asarray(x)[v], cfg)
    assert stats.tolist() == stats2.tolist() == [
        14 * cfg.top_k, len(np.unique(np.asarray(experts)))]


@pytest.mark.parametrize("sizes", [
    [5, 0, 12, 3, 0, 0, 9, 4],            # rows past the groups
    [0, 0, 0, 140, 0, 0, 0, 60],          # groups straddling row tiles
    [1, 1, 1, 0, 1, 1, 1, 0],             # a decode step: one tile
    [0] * 8,                              # no rows at all
])
def test_kernel_equals_oracle(sizes):
    k = jax.random.split(KEY, 4)
    e, d, f = 8, 32, 256
    rows = max(sum(sizes), 6) + 4
    x = jax.random.normal(k[0], (rows, d))
    w = [jax.random.normal(k[i], s) * 0.2 for i, s in
         ((1, (e, d, f)), (2, (e, d, f)), (3, (e, f, d)))]
    sizes = jnp.asarray(sizes, jnp.int32)
    got = moe_gmm(x, sizes, *w, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(moe_gmm_ref(x, sizes, *w)),
                               rtol=1e-5, atol=1e-5)
    assert not np.any(np.asarray(got)[int(sizes.sum()):])


def test_gradient_is_the_oracles():
    cfg = _cfg()
    p, x = _layer(cfg, 9)

    def loss(p, x):
        return (moe.moe_ffn(p, x, cfg)[0] ** 2).sum()

    g = jax.grad(loss, argnums=(0, 1))(p, x)
    flat = x.reshape(-1, cfg.d_model)
    w, experts = moe.route(p, flat, cfg)

    def dense(p, x):
        # every expert on every token, weighted by the gate (0 if unpicked)
        gate = jnp.zeros((x.shape[0], cfg.n_experts)).at[
            jnp.arange(x.shape[0])[:, None], experts].set(w)
        ys = jax.vmap(lambda a, b, c: _swiglu(x, a, b, c))(
            p["wg"], p["wu"], p["wd"])
        sp = p["shared"]
        out = jnp.einsum("ne,end->nd", gate, ys) + _swiglu(
            x, sp["wg"], sp["wu"], sp["wd"])
        return (out ** 2).sum()

    want = jax.grad(dense, argnums=(0, 1))(p, x)
    for n in ("wg", "wu", "wd"):
        np.testing.assert_allclose(np.asarray(g[0][n]),
                                   np.asarray(want[0][n]), rtol=1e-4,
                                   atol=1e-5)
