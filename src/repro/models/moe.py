"""Dropless Mixture-of-Experts FFN: softmax gate, greedy top-k, grouped
expert matmul.

Every (token, expert) pair the gate picks is computed; none is dropped, so
a token's output depends on its own routing only, not on what it is
batched with. The rows are sorted by expert and the routed experts' SwiGLU
runs as one grouped matmul over the experts that got rows
(`repro.kernels.moe_gmm`): a decode step reads ``top_k`` experts' weights,
not all of them. Positions marked invalid (the padding of a bucketed
prefill or extend) send no row to any expert.

The gate follows the published DeepSeek/Mixtral form: float32 router
logits and softmax, top-k, the k weights renormalised to sum 1 only where
``cfg.norm_topk_prob``, then times ``cfg.routed_scaling_factor``. Shared
experts (DeepSeek) are one SwiGLU of width ``n_shared_experts * moe_d_ff``
that every token takes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.ref import moe_gmm_ref
from repro.models.layers import normal_init


def moe_init(key, cfg, dtype):
    d = cfg.d_model
    e_ff = cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": normal_init(ks[0], (d, cfg.n_experts), d, dtype),
        "wg": normal_init(ks[1], (cfg.n_experts, d, e_ff), d, dtype),
        "wu": normal_init(ks[2], (cfg.n_experts, d, e_ff), d, dtype),
        "wd": normal_init(ks[3], (cfg.n_experts, e_ff, d), e_ff, dtype,
                          scale=1.0 / max(2 * cfg.n_layers, 1) ** 0.5),
    }
    if cfg.n_shared_experts:
        sf = cfg.n_shared_experts * e_ff
        kg, ku, kd = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wg": normal_init(kg, (d, sf), d, dtype),
            "wu": normal_init(ku, (d, sf), d, dtype),
            "wd": normal_init(kd, (sf, d), sf, dtype),
        }
    return p


def moe_axes(cfg):
    ax = {
        "router": "embed expert",
        "wg": "expert embed ff",
        "wu": "expert embed ff",
        "wd": "expert ff embed",
    }
    if cfg.n_shared_experts:
        ax["shared"] = {"wg": "embed ff", "wu": "embed ff", "wd": "ff embed"}
    return ax


def route(p, x, cfg):
    """The gate. x: [N, D] -> (weights [N, k] float32, experts [N, k]).
    Its projection runs in float32 at full precision, as the published
    gate computes it: the top-k choice is a discontinuity, and rounding
    its logits to the activations' dtype would flip near ties."""
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    weights, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                     cfg.top_k)
    if cfg.norm_topk_prob:
        weights = weights / weights.sum(-1, keepdims=True)
    return weights * cfg.routed_scaling_factor, experts


#: the routed experts' weights, which a stacked layer keeps whole
EXPERT_WEIGHTS = ("wg", "wu", "wd")


@jax.custom_vjp
def _experts(xs, sizes, wg, wu, wd, layer):
    return moe_gmm(xs, sizes, wg, wu, wd, layer)


def _experts_fwd(xs, sizes, wg, wu, wd, layer):
    return moe_gmm(xs, sizes, wg, wu, wd, layer), (xs, sizes, wg, wu, wd,
                                                   layer)


def _experts_bwd(res, g):
    # the kernel has no autodiff rule: the gradients are its oracle's
    xs, sizes, wg, wu, wd, layer = res

    def ref(a, b, c, d):
        if layer is not None:
            b, c, d = b[layer], c[layer], d[layer]
        return moe_gmm_ref(a, sizes, b, c, d)

    dx, dwg, dwu, dwd = jax.vjp(ref, xs, wg, wu, wd)[1](g)
    return dx, None, dwg, dwu, dwd, None


_experts.defvjp(_experts_fwd, _experts_bwd)


def moe_ffn(p, x, cfg, valid=None, layer=None):
    """x: [..., D]; ``valid`` (x's leading shape, bool) marks the positions
    that route (None: all). Where ``layer`` is given, the routed experts'
    weights (``EXPERT_WEIGHTS``) carry the stack's leading layer axis and
    ``layer`` picks this one (the kernel reads it in place). Returns (out
    [..., D], stats int32[2]): the (token, expert) rows the routed experts
    computed and the experts that got at least one."""
    lead, d = x.shape[:-1], x.shape[-1]
    e, k = cfg.n_experts, cfg.top_k
    xt = x.reshape(-1, d)
    weights, experts = route(p, xt, cfg)            # [N, k]
    flat = experts.reshape(-1)                      # row r = token r // k
    if valid is not None:
        flat = jnp.where(jnp.repeat(valid.reshape(-1), k), flat, e)
    # rows sorted by expert; invalid rows (expert e) sort last and join
    # no group
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((e,), jnp.int32).at[flat].add(1, mode="drop")
    ys = _experts(xt[order // k], sizes, p["wg"], p["wu"], p["wd"], layer)
    # back to (token, slot) order: a row gather by the inverse permutation
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
    y = ys[inv]
    out = jnp.einsum("nkd,nk->nd", y.reshape(-1, k, d).astype(jnp.float32),
                     weights).astype(x.dtype)
    if cfg.n_shared_experts:
        sp = p["shared"]
        h = jax.nn.silu(xt @ sp["wg"]) * (xt @ sp["wu"])
        out = out + h @ sp["wd"]
    stats = jnp.stack([sizes.sum(), (sizes > 0).sum()]).astype(jnp.int32)
    return out.reshape(*lead, d), stats
