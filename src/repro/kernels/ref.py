"""Pure-jnp/numpy oracles for every Pallas kernel (ground truth in tests).

These are intentionally the SIMPLEST possible implementations (stepwise
recurrences, dense masked attention, python-loop LCP) — slow but obviously
correct. Kernels and the models' optimized jnp paths are both validated
against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# ---------------- LCP ----------------

def lcp_ref(prompts: np.ndarray, ledgers: np.ndarray) -> np.ndarray:
    """prompts: [N, L] int32; ledgers: [N, M, L] int32 -> [N, M] int32."""
    n, l = prompts.shape
    m = ledgers.shape[1]
    out = np.zeros((n, m), np.int32)
    for j in range(n):
        for i in range(m):
            c = 0
            while c < l and prompts[j, c] == ledgers[j, i, c]:
                c += 1
            out[j, i] = c
    return out


# ---------------- auction bidding round ----------------

def auction_bid_ref(W, ask, ask2, active, eps):
    """One Jacobi forward-bidding round of the capacitated column market,
    pure jnp (the kernel's oracle).

    W: [n, m] agent-level weights; ask/ask2: [m] cheapest and
    second-cheapest unit price per agent (segment-min/-min2 over the
    agent's capacity counter, +big where the agent has fewer units);
    active: [n] bool; eps scalar.  Returns (best [m], winner [m] int32,
    wants [n] bool) — the segment-max bid per agent, the winning request
    per agent (ties to the lowest index, n where no bid), and which active
    requests bid at all (top profit > 0).

    The runner-up value v2 substitutes the favourite agent's own
    second-cheapest unit (ask2) at the k1 column — the column market's
    equivalent of masking out the single chosen slot in a slot-expanded
    round.
    """
    W = jnp.asarray(W)
    ask = jnp.asarray(ask, W.dtype)
    ask2 = jnp.asarray(ask2, W.dtype)
    active = jnp.asarray(active, bool)
    n, m = W.shape
    big = jnp.asarray(jnp.finfo(W.dtype).max / 4, W.dtype)
    P = jnp.where(active[:, None], W - ask[None, :], -big)
    v1 = P.max(axis=1)
    k1 = P.argmax(axis=1)
    onehot = jnp.arange(m)[None, :] == k1[:, None]
    alt = jnp.where(onehot & active[:, None], W - ask2[None, :], P)
    v2 = jnp.maximum(alt.max(axis=1), 0.0)
    wants = active & (v1 > 0.0)
    bid = ask[k1] + (v1 - v2) + eps
    best = jnp.full((m,), -big, W.dtype).at[
        jnp.where(wants, k1, m)].max(bid, mode="drop")
    at_best = wants & (bid == best[jnp.minimum(k1, m - 1)])
    winner = jnp.full((m,), n, jnp.int32).at[
        jnp.where(at_best, k1, m)].min(
            jnp.arange(n, dtype=jnp.int32), mode="drop")
    return best, winner, wants


# ---------------- attention ----------------

def attention_ref(q, k, v, *, causal=True, window=0, scale=None):
    """q: [B,Sq,H,d], k/v: [B,Sk,Hkv,d] (GQA by head grouping)."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, d).astype(jnp.float32)
    s = jnp.einsum("bskgd,btkd->bkgst", qg, k.astype(jnp.float32))
    s = s * (scale or 1.0 / np.sqrt(d))
    qpos = np.arange(sq)[:, None]
    kpos = np.arange(k.shape[1])[None, :]
    mask = np.ones((sq, k.shape[1]), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= (qpos - kpos) < window
    s = jnp.where(jnp.asarray(mask)[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, h, d).astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, valid):
    """q: [B,H,d]; caches: [B,M,Hkv,d]; valid: [B,M] bool."""
    b, h, d = q.shape
    hkv = k_cache.shape[2]
    qg = q.reshape(b, hkv, h // hkv, d).astype(jnp.float32)
    s = jnp.einsum("bkgd,bmkd->bkgm", qg, k_cache.astype(jnp.float32))
    s = s / np.sqrt(d)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgm,bmkd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, h, d).astype(q.dtype)


# ---------------- WKV6 (stepwise recurrence) ----------------

def wkv6_ref(r, k, v, log_w, u, s0):
    """r,k,v,log_w: [B,S,H,dk] (dv == dk); u: [H,dk]; s0: [B,H,dk,dv].

    o_t = r_t @ (S_{t-1} + (u*k_t)^T v_t);  S_t = diag(w_t) S_{t-1} + k_t^T v_t
    """
    r = jnp.asarray(r, jnp.float32)
    k = jnp.asarray(k, jnp.float32)
    v = jnp.asarray(v, jnp.float32)
    log_w = jnp.asarray(log_w, jnp.float32)
    u = jnp.asarray(u, jnp.float32)

    def step(s, inp):
        rt, kt, vt, lwt = inp
        kv = jnp.einsum("bhd,bhv->bhdv", kt, vt)
        o = jnp.einsum("bhd,bhdv->bhv", rt, s + u[None, :, :, None] * kv)
        s = s * jnp.exp(lwt)[..., None] + kv
        return s, o

    xs = tuple(x.swapaxes(0, 1) for x in (r, k, v, log_w))
    sT, o = jax.lax.scan(step, jnp.asarray(s0, jnp.float32), xs)
    return o.swapaxes(0, 1), sT


# ---------------- SSD / Mamba2 (stepwise recurrence) ----------------

def ssd_ref(x, bmat, cmat, dt, a_log, d_skip, s0):
    """x: [B,S,H,hd]; bmat,cmat: [B,S,ds]; dt: [B,S,H]; s0: [B,H,hd,ds].

    S_t = a_t S_{t-1} + dt_t (x_t outer B_t);  y_t = S_t @ C_t + D * x_t
    """
    x = jnp.asarray(x, jnp.float32)
    bmat = jnp.asarray(bmat, jnp.float32)
    cmat = jnp.asarray(cmat, jnp.float32)
    dt = jnp.asarray(dt, jnp.float32)
    a = lambda dtt: jnp.exp(-jnp.exp(jnp.asarray(a_log, jnp.float32))[None] * dtt)

    def step(s, inp):
        xt, bt, ct, dtt = inp
        s = s * a(dtt)[..., None, None] + jnp.einsum(
            "bh,bhd,bn->bhdn", dtt, xt, bt)
        y = jnp.einsum("bhdn,bn->bhd", s, ct)
        y = y + jnp.asarray(d_skip, jnp.float32)[None, :, None] * xt
        return s, y

    xs = (x.swapaxes(0, 1), bmat.swapaxes(0, 1), cmat.swapaxes(0, 1),
          dt.swapaxes(0, 1))
    sT, y = jax.lax.scan(step, jnp.asarray(s0, jnp.float32), xs)
    return y.swapaxes(0, 1), sT


# ---------------- grouped expert FFN ----------------

def moe_gmm_ref(x, sizes, wg, wu, wd):
    """The routed experts' SwiGLU over rows sorted by expert, in pure jnp
    (the kernel's oracle and its backward pass). x: [R, D]; sizes: [E]
    rows per expert; wg, wu: [E, D, F]; wd: [E, F, D]. Rows past
    ``sum(sizes)`` read as zeros."""
    g = jax.lax.ragged_dot(x, wg, sizes, preferred_element_type=jnp.float32)
    u = jax.lax.ragged_dot(x, wu, sizes, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    return jax.lax.ragged_dot(h, wd, sizes,
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)
