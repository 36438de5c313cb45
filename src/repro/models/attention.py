"""Attention: GQA / MHA / sliding-window / MLA, parallel and cached-decode forms.

Conventions
-----------
* Parallel form (training / prefill): q,k,v are [B, S, H(. kv), hd]; causal
  (+ optional sliding window, + optional per-sequence valid-length mask for
  right-padded prompts).
* Decode form: q is [B, H, hd] for ONE new token per sequence; the KV cache
  is [B, M, Hkv, hd] with a per-slot absolute-position array ``slot_pos``
  ([B, M], -1 = empty). Sliding-window caches are ring buffers of size W —
  slot_pos makes ring masking trivial and exact.
* The pure-jnp paths here are the reference implementation; Pallas kernels in
  repro.kernels implement the same math for TPU (validated vs these).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard

NEG_INF = -1e30


def _group(q, n_kv: int):
    """[B, S, H, hd] -> [B, S, Kv, G, hd]."""
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


CHUNK_Q_THRESHOLD = 8192  # dense scores above this switch to the chunked path
CHUNK_Q = 1024


def attend_parallel(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset=0, kv_valid_len=None, scale=None):
    """Full parallel attention; GQA handled by broadcasting K/V to H heads so
    the [B, H, Sq, Sk] score tensor shards over the FULL head count (8 KV
    heads cannot divide a 16-way model axis; 64 query heads can).

    For Sq above CHUNK_Q_THRESHOLD, scores are computed in q-chunks via
    ``layer_scan`` (flash-style online pass, bounded HBM; unrollable for the
    dry-run cost variants).

    q: [B, Sq, H, hd]; k, v: [B, Sk, Hkv, hd].
    q_offset: absolute position of q[0] minus kv[0] (chunked prefill support).
    kv_valid_len: [B] valid key length (right-padded prompts).
    scale: the softmax scale (None: 1/sqrt(hd)).
    """
    from repro.models.scan_config import layer_scan

    b, sq, h, hd = q.shape
    sk, n_kv = k.shape[1], k.shape[2]
    if scale is None:
        scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    k_pos = jnp.arange(sk)
    kmask = None
    if kv_valid_len is not None:
        kmask = k_pos[None, :] < kv_valid_len[:, None]  # [B,Sk]
    q_pos = jnp.arange(sq) + q_offset

    def qmask(pos_blk):
        m = jnp.ones((pos_blk.shape[0], sk), bool)
        if causal:
            m &= k_pos[None, :] <= pos_blk[:, None]
        if window:
            m &= (pos_blk[:, None] - k_pos[None, :]) < window
        return m

    if sq <= CHUNK_Q_THRESHOLD or sq % CHUNK_Q != 0:
        # Dense path: q keeps its SEQUENCE sharding (only the small grouped
        # K/V are gathered over seq), avoiding any gather of the residual.
        q = shard(q, "batch", "seq", "attn_head", "head_dim")
        k = shard(k, "batch", "attn_kv_seq", "attn_head", "head_dim")
        v = shard(v, "batch", "attn_kv_seq", "attn_head", "head_dim")
        qg = _group(q, n_kv)  # [B,Sq,Kv,G,hd]
        s = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
        s = shard(s, "batch", "attn_head", "attn_head", "seq", "attn_kv_seq")
        m = qmask(q_pos)
        s = jnp.where(m[None, None, None], s, NEG_INF)
        if kmask is not None:
            s = jnp.where(kmask[:, None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgst,btkd->bskgd", p, v)
        return out.reshape(b, sq, h, v.shape[-1])

    # Chunked long-context path: q-chunks stream through a flash-style scan
    # (unrollable for dry-run cost variants). When the full head count
    # divides the model axis, K/V are repeated and scores shard over heads;
    # otherwise (e.g. 40/56 heads on a 16-way axis) the GQA-grouped einsum
    # avoids the repeat entirely and a smaller chunk bounds the replicated
    # score tensor (EXPERIMENTS.md §Perf iteration 2).
    from repro.distributed.sharding import current_policy

    policy = current_policy()
    msize = policy.mesh.shape.get("model", 1) if policy else 1
    heads_shardable = h % max(msize, 1) == 0
    chunk = CHUNK_Q if heads_shardable else 128
    if sq % chunk != 0:
        chunk = sq  # fallback (callers keep power-of-two seqs)

    if heads_shardable:
        if n_kv != h:
            k = jnp.repeat(k, h // n_kv, axis=2)
            v = jnp.repeat(v, h // n_kv, axis=2)
        q = shard(q, "batch", "attn_seq", "heads", "head_dim")
        k = shard(k, "batch", "attn_kv_seq", "heads", "head_dim")
        v = shard(v, "batch", "attn_kv_seq", "heads", "head_dim")

        def block(q_blk, pos_blk):
            s = jnp.einsum("bshd,bthd->bhst", q_blk, k).astype(jnp.float32) * scale
            s = shard(s, "batch", "heads", "attn_seq", "attn_kv_seq")
            s = jnp.where(qmask(pos_blk)[None, None], s, NEG_INF)
            if kmask is not None:
                s = jnp.where(kmask[:, None, None], s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
            return jnp.einsum("bhst,bthd->bshd", p, v)
    else:
        def block(q_blk, pos_blk):
            qg = _group(q_blk, n_kv)  # [B,c,Kv,G,hd]
            s = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
            s = jnp.where(qmask(pos_blk)[None, None, None], s, NEG_INF)
            if kmask is not None:
                s = jnp.where(kmask[:, None, None, None], s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
            o = jnp.einsum("bkgst,btkd->bskgd", p, v)
            return o.reshape(*q_blk.shape[:2], h, v.shape[-1])

    nq = sq // chunk
    q_ch = q.reshape(b, nq, chunk, h, hd).swapaxes(0, 1)
    pos_ch = q_pos.reshape(nq, chunk)

    def body(carry, xs):
        qb, pb = xs
        return carry, block(qb, pb)

    _, out = layer_scan(body, 0, (q_ch, pos_ch))
    out = out.swapaxes(0, 1).reshape(b, sq, h, v.shape[-1])
    return out


def attend_decode(q, k_cache, v_cache, slot_pos, pos, *, window: int = 0):
    """One-token attention against a cache.

    q: [B, H, hd]; k_cache/v_cache: [B, M, Hkv, hd]; slot_pos: [B, M] absolute
    positions (-1 empty); pos: [B] current query positions.
    """
    b, h, hd = q.shape
    n_kv = k_cache.shape[2]
    qg = q.reshape(b, n_kv, h // n_kv, hd)
    scores = jnp.einsum("bkgd,bmkd->bkgm", qg, k_cache).astype(jnp.float32)
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    valid = (slot_pos >= 0) & (slot_pos <= pos[:, None])
    if window:
        valid &= (pos[:, None] - slot_pos) < window
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgm,bmkd->bkgd", probs, v_cache)
    return out.reshape(b, h, hd)


def cache_append(k_cache, v_cache, slot_pos, k_new, v_new, pos, *, window: int = 0):
    """Append one token's k,v at per-sequence positions (ring buffer if window).

    k_new/v_new: [B, Hkv, hd]; pos: [B]. Returns updated (k, v, slot_pos).
    """
    m = k_cache.shape[1]
    # ring modulus is the cache size (min(window, max_len)), matching
    # prefill_cache_layout / cache_extend
    slot = (pos % m) if window else jnp.minimum(pos, m - 1)

    def upd(cache, new, s):
        return jax.lax.dynamic_update_slice(cache, new[None], (s, 0, 0))

    k_cache = jax.vmap(upd)(k_cache, k_new, slot)
    v_cache = jax.vmap(upd)(v_cache, v_new, slot)
    slot_pos = jax.vmap(lambda sp, s, p: sp.at[s].set(p))(slot_pos, slot, pos)
    return k_cache, v_cache, slot_pos


def prefill_cache_layout(k, v, lens, max_len: int, *, window: int = 0):
    """Lay prefill K/V into a decode cache. k,v: [B,S,Hkv,hd]; lens: [B].

    Returns (k_cache, v_cache, slot_pos) of length M = max_len (or W for SWA).
    For sliding windows the last W positions land in ring order.
    """
    b, s, hkv, hd = k.shape
    m = min(window, max_len) if window else max_len
    pos = jnp.arange(s)
    if not window and m >= s:
        # Fast path (no ring wrap): the cache IS the padded K/V — a masked
        # copy that keeps the sequence sharding intact (no scatter; GSPMD
        # would otherwise replicate multi-GB caches, §Perf iteration 1).
        keep = pos[None, :] < lens[:, None]
        pad = ((0, 0), (0, m - s), (0, 0), (0, 0))
        k_cache = jnp.pad(jnp.where(keep[..., None, None], k, 0.0), pad)
        v_cache = jnp.pad(jnp.where(keep[..., None, None], v, 0.0), pad)
        slot_pos = jnp.pad(jnp.where(keep, pos[None, :], -1),
                           ((0, 0), (0, m - s)), constant_values=-1)
        return k_cache, v_cache, slot_pos.astype(jnp.int32)
    slot = (pos % m) if window else jnp.minimum(pos, m - 1)
    # Only the last m valid positions of each sequence can live in the ring;
    # each ring slot then receives at most ONE kept position, so scatter-add
    # on zero-init caches is deterministic even with duplicate slot indices.
    keep = (pos[None, :] < lens[:, None]) & (pos[None, :] >= lens[:, None] - m)
    k_cache = jnp.zeros((b, m, hkv, hd), k.dtype)
    v_cache = jnp.zeros((b, m, hkv, hd), v.dtype)
    slot_pos = jnp.full((b, m), -1, jnp.int32)
    k_cache = k_cache.at[:, slot].add(jnp.where(keep[..., None, None], k, 0.0))
    v_cache = v_cache.at[:, slot].add(jnp.where(keep[..., None, None], v, 0.0))
    slot_pos = slot_pos.at[:, slot].max(jnp.where(keep, pos[None, :], -1))
    return k_cache, v_cache, slot_pos


def attend_mixed(q, k_new, v_new, k_cache, v_cache, slot_pos, pos0, lens_new,
                 *, window: int = 0, scale=None):
    """Chunked-prefill attention: new tokens attend to (cache + new block).

    q, k_new, v_new: [B, Sn, H(kv), hd]; caches: [B, M, Hkv, hd];
    pos0: [B] absolute position of the first new token; lens_new: [B];
    scale: the softmax scale (None: 1/sqrt(hd)).
    Used by the serving engine for multi-turn KV reuse (the paper's o_ij).
    """
    b, sn, h, hd = q.shape

    def scaled(scores):
        scores = scores.astype(jnp.float32)
        return scores / jnp.sqrt(hd) if scale is None else scores * scale

    n_kv = k_new.shape[2]
    qg = _group(q, n_kv)
    q_pos = pos0[:, None] + jnp.arange(sn)[None, :]  # [B,Sn]

    # scores vs cache slots
    sc = scaled(jnp.einsum("bskgd,bmkd->bkgsm", qg, k_cache))
    valid_c = (slot_pos >= 0)[:, None, :] & (slot_pos[:, None, :] <= q_pos[..., None])
    if window:
        valid_c &= (q_pos[..., None] - slot_pos[:, None, :]) < window
    sc = jnp.where(valid_c[:, None, None], sc, NEG_INF)  # [B,Sn,M]->[B,1,1,Sn,M]

    # scores vs new block (causal within block, length-masked)
    sb = scaled(jnp.einsum("bskgd,btkd->bkgst", qg, k_new))
    t_idx = jnp.arange(sn)
    mask_b = (t_idx[None, :, None] >= t_idx[None, None, :])  # s >= t (causal)
    mask_b = mask_b & (t_idx[None, None, :] < lens_new[:, None, None])
    if window:
        mask_b = mask_b & ((t_idx[None, :, None] - t_idx[None, None, :]) < window)
    sb = jnp.where(mask_b[:, None, None], sb, NEG_INF)

    scores = jnp.concatenate([sc, sb], axis=-1)  # [B,Kv,G,Sn,M+Sn]
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    v_all = jnp.concatenate([v_cache, v_new], axis=1)
    out = jnp.einsum("bkgsm,bmkd->bskgd", probs, v_all)
    return out.reshape(b, sn, h, v_new.shape[-1])


def cache_extend(k_cache, v_cache, slot_pos, k_new, v_new, pos0, lens_new,
                 *, window: int = 0):
    """Scatter a block of new K/V into the cache at positions pos0..pos0+len.

    Deterministic under ring-buffer wraparound (keep-last-W semantics).
    """
    b, sn, hkv, hd = k_new.shape
    m = k_cache.shape[1]
    t = jnp.arange(sn)
    pos = pos0[:, None] + t[None, :]  # [B,Sn]
    slot = (pos % m) if window else jnp.minimum(pos, m - 1)
    keep = (t[None, :] < lens_new[:, None]) & (pos >= pos0[:, None] + lens_new[:, None] - m)

    # zero the slots being overwritten first (mask out stale entries), then
    # scatter-add: each slot receives at most one kept position, so this is
    # deterministic even with duplicate slot indices from ring wraparound.
    def row_fn(kc, vc, sp, kn, vn, sl, kp, p_row):
        hit = jnp.zeros((m,), bool).at[sl].set(kp, mode="drop")
        kc = jnp.where(hit[:, None, None], jnp.zeros_like(kc), kc)
        vc = jnp.where(hit[:, None, None], jnp.zeros_like(vc), vc)
        sp = jnp.where(hit, -1, sp)
        kc = kc.at[sl].add(jnp.where(kp[:, None, None], kn, 0.0))
        vc = vc.at[sl].add(jnp.where(kp[:, None, None], vn, 0.0))
        sp = sp.at[sl].max(jnp.where(kp, p_row, -1))
        return kc, vc, sp

    k_cache, v_cache, slot_pos = jax.vmap(row_fn)(
        k_cache, v_cache, slot_pos, k_new, v_new, slot, keep, pos)
    return k_cache, v_cache, slot_pos


# ---------------- parameterized attention blocks ----------------

def gqa_init(key, cfg, dtype):
    from repro.models.layers import normal_init

    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": normal_init(ks[0], (d, h, hd), d, dtype),
        "wk": normal_init(ks[1], (d, kv, hd), d, dtype),
        "wv": normal_init(ks[2], (d, kv, hd), d, dtype),
        "wo": normal_init(ks[3], (h, hd, d), h * hd, dtype,
                          scale=1.0 / max(2 * cfg.n_layers, 1) ** 0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dtype)
        p["bk"] = jnp.zeros((kv, hd), dtype)
        p["bv"] = jnp.zeros((kv, hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def gqa_axes(cfg):
    ax = {
        "wq": "embed heads head_dim",
        "wk": "embed kv_heads head_dim",
        "wv": "embed kv_heads head_dim",
        "wo": "heads head_dim embed",
    }
    if cfg.qkv_bias:
        ax.update(bq="heads head_dim", bk="kv_heads head_dim", bv="kv_heads head_dim")
    if cfg.qk_norm:
        ax.update(q_norm="head_dim", k_norm="head_dim")
    return ax


def _qkv(p, x, cfg):
    from repro.models.layers import rms_norm

    q = jnp.einsum("...d,dhk->...hk", x, p["wq"])
    k = jnp.einsum("...d,dhk->...hk", x, p["wk"])
    v = jnp.einsum("...d,dhk->...hk", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def gqa_parallel(p, x, cfg, *, lens=None, pos0=0):
    """x: [B,S,D] -> (out [B,S,D], (k, v) for cache layout)."""
    from repro.models.layers import apply_rope

    q, k, v = _qkv(p, x, cfg)
    pos = jnp.arange(x.shape[1]) + pos0
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    o = attend_parallel(q, k, v, causal=True, window=cfg.sliding_window,
                        kv_valid_len=lens)
    out = jnp.einsum("...hk,hkd->...d", o, p["wo"])
    return out, (k, v)


def gqa_decode(p, x, cache_layer, cfg):
    """x: [B,D] one token; cache_layer: dict(k, v, slot_pos); pos: [B]."""
    from repro.models.layers import apply_rope

    pos = cache_layer["pos"]
    q, k, v = _qkv(p, x[:, None, :], cfg)  # [B,1,H,hd]
    q = apply_rope(q, pos[:, None], cfg.rope_theta)[:, 0]
    k = apply_rope(k, pos[:, None], cfg.rope_theta)[:, 0]
    v = v[:, 0]
    kc, vc, sp = cache_append(cache_layer["k"], cache_layer["v"],
                              cache_layer["slot_pos"], k, v, pos,
                              window=cfg.sliding_window)
    o = attend_decode(q, kc, vc, sp, pos, window=cfg.sliding_window)
    out = jnp.einsum("bhk,hkd->bd", o, p["wo"])
    new_cache = {"k": kc, "v": vc, "slot_pos": sp, "pos": pos + 1}
    return out, new_cache


def gqa_extend(p, x, cache_layer, cfg, lens_new):
    """Process a block of new tokens attending to cache + block (multi-turn).

    x: [B, Sn, D]; cache_layer: dict(k, v, slot_pos, pos). Returns
    (out [B,Sn,D], new cache_layer with pos advanced by lens_new).
    """
    from repro.models.layers import apply_rope

    pos0 = cache_layer["pos"]
    q, k, v = _qkv(p, x, cfg)
    pos = pos0[:, None] + jnp.arange(x.shape[1])[None, :]
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    o = attend_mixed(q, k, v, cache_layer["k"], cache_layer["v"],
                     cache_layer["slot_pos"], pos0, lens_new,
                     window=cfg.sliding_window)
    kc, vc, sp = cache_extend(cache_layer["k"], cache_layer["v"],
                              cache_layer["slot_pos"], k, v, pos0, lens_new,
                              window=cfg.sliding_window)
    out = jnp.einsum("...hk,hkd->...d", o, p["wo"])
    new_cache = {"k": kc, "v": vc, "slot_pos": sp, "pos": pos0 + lens_new}
    return out, new_cache


# ---------------- MLA (DeepSeek-V2) ----------------

def mla_init(key, cfg, dtype):
    from repro.models.layers import normal_init

    d, h = cfg.d_model, cfg.n_heads
    nope, rope_d, vd, lora = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    ks = jax.random.split(key, 5)
    return {
        "wq": normal_init(ks[0], (d, h, nope + rope_d), d, dtype),
        "wdkv": normal_init(ks[1], (d, lora + rope_d), d, dtype),
        "kv_norm": jnp.ones((lora,), dtype),
        "wuk": normal_init(ks[2], (lora, h, nope), lora, dtype),
        "wuv": normal_init(ks[3], (lora, h, vd), lora, dtype),
        "wo": normal_init(ks[4], (h, vd, d), h * vd, dtype,
                          scale=1.0 / max(2 * cfg.n_layers, 1) ** 0.5),
    }


def mla_axes(cfg):
    return {
        "wq": "embed heads qk_dim",
        "wdkv": "embed kv_lora",
        "kv_norm": "kv_lora",
        "wuk": "kv_lora heads qk_dim",
        "wuv": "kv_lora heads head_dim",
        "wo": "heads head_dim embed",
    }


def mla_softmax_scale(cfg) -> float:
    """The MLA softmax scale: ``(qk_nope + qk_rope)^-0.5``, times YaRN's
    ``mscale(factor, mscale_all_dim)^2`` where the rope is YaRN-scaled."""
    from repro.models.layers import yarn_mscale

    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return scale


def _mla_rope(x, pos, cfg):
    """Rotary embedding of MLA's rope dimensions (YaRN where configured)."""
    from repro.models.layers import apply_rope

    return apply_rope(x, pos, cfg.rope_theta, cfg.rope_scaling)


def _mla_qkv_from_latent(p, ckv, krope, cfg):
    """Expand cached latent to per-head K/V. ckv: [..., lora], krope: [..., rope]."""
    k_nope = jnp.einsum("...l,lhn->...hn", ckv, p["wuk"])
    v = jnp.einsum("...l,lhv->...hv", ckv, p["wuv"])
    k_rope = jnp.broadcast_to(
        krope[..., None, :], (*k_nope.shape[:-1], cfg.qk_rope_dim)
    )
    k = jnp.concatenate([k_nope, k_rope], axis=-1)
    return k, v


def mla_parallel(p, x, cfg, *, lens=None, pos0=0):
    from repro.models.layers import rms_norm

    b, s, _ = x.shape
    pos = jnp.arange(s) + pos0
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    q_rope = _mla_rope(q_rope, pos, cfg)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    dkv = jnp.einsum("bsd,dl->bsl", x, p["wdkv"])
    ckv, krope = jnp.split(dkv, [cfg.kv_lora_rank], axis=-1)
    ckv = rms_norm(ckv, p["kv_norm"], cfg.norm_eps)
    krope = _mla_rope(krope[:, :, None, :], pos, cfg)[:, :, 0]
    k, v = _mla_qkv_from_latent(p, ckv, krope, cfg)
    q = shard(q, "batch", "seq", "heads", "qk_dim")
    o = attend_parallel(q, k, v, causal=True, kv_valid_len=lens,
                        scale=mla_softmax_scale(cfg))
    out = jnp.einsum("bshv,hvd->bsd", o, p["wo"])
    return out, (ckv, krope)


# Absorbed MLA decode (DeepSeek-V2 §"absorb"): fold W_uk into the query and
# W_uv into the output so attention runs entirely in the compressed latent
# space — per step O(M·lora) instead of O(M·lora·H·(nope+vd)) expansion.
# Default ON: 56x fewer decode flops and 3.5x fewer bytes on the
# deepseek-v2-lite decode_32k cell (EXPERIMENTS.md §Perf It.6); equivalence
# vs the naive path is tested in tests/test_models.py.
MLA_ABSORBED = True


def mla_decode(p, x, cache_layer, cfg, *, absorbed: bool | None = None):
    from repro.models.layers import rms_norm

    if absorbed is None:
        absorbed = MLA_ABSORBED
    pos = cache_layer["pos"]
    q = jnp.einsum("bd,dhk->bhk", x, p["wq"])
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    q_rope = _mla_rope(q_rope[:, None], pos[:, None], cfg)[:, 0]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    dkv = jnp.einsum("bd,dl->bl", x, p["wdkv"])
    ckv_new, krope_new = jnp.split(dkv, [cfg.kv_lora_rank], axis=-1)
    ckv_new = rms_norm(ckv_new, p["kv_norm"], cfg.norm_eps)
    krope_new = _mla_rope(krope_new[:, None, None], pos[:, None], cfg)[:, 0, 0]

    m = cache_layer["ckv"].shape[1]
    slot = jnp.minimum(pos, m - 1)
    upd = lambda c, n, s: jax.lax.dynamic_update_slice(c, n[None], (s, 0))
    ckv_c = jax.vmap(upd)(cache_layer["ckv"], ckv_new, slot)
    kr_c = jax.vmap(upd)(cache_layer["krope"], krope_new, slot)
    sp = jax.vmap(lambda v_, s, p_: v_.at[s].set(p_))(cache_layer["slot_pos"], slot, pos)

    valid = (sp >= 0) & (sp <= pos[:, None])
    scale = mla_softmax_scale(cfg)
    if absorbed:
        # scores = q_nope^T W_uk ckv + q_rope^T k_rope, all in latent space
        q_abs = jnp.einsum("bhn,lhn->bhl", q_nope, p["wuk"])  # [B,H,lora]
        scores = (jnp.einsum("bhl,bml->bhm", q_abs, ckv_c)
                  + jnp.einsum("bhr,bmr->bhm", q_rope, kr_c)).astype(jnp.float32)
        scores = scores * scale
        scores = jnp.where(valid[:, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        o_lat = jnp.einsum("bhm,bml->bhl", probs, ckv_c)      # [B,H,lora]
        o = jnp.einsum("bhl,lhv->bhv", o_lat, p["wuv"])
    else:
        # naive: expand all cached latents to per-head K/V each step
        k, v = _mla_qkv_from_latent(p, ckv_c, kr_c, cfg)  # [B,M,H,*]
        scores = jnp.einsum("bhk,bmhk->bhm", q, k).astype(jnp.float32) * scale
        scores = jnp.where(valid[:, None, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        o = jnp.einsum("bhm,bmhv->bhv", probs, v)
    out = jnp.einsum("bhv,hvd->bd", o, p["wo"])
    new_cache = {"ckv": ckv_c, "krope": kr_c, "slot_pos": sp, "pos": pos + 1}
    return out, new_cache


def latent_extend(ckv, krope, slot_pos, ckv_new, krope_new, pos0, lens_new):
    """Write a block of new latents into an MLA cache at positions
    pos0..pos0+len (no ring: MLA is full attention). ckv/krope: [B, M, *];
    the new ones [B, Sn, *]. Returns (ckv, krope, slot_pos).

    The new latents replace what the slots held: after the engine truncates
    a cache (``slot_pos`` reset past the kept prefix) those slots still
    hold the discarded tokens' latents."""
    m = ckv.shape[1]
    t = jnp.arange(ckv_new.shape[1])
    pos = pos0[:, None] + t[None, :]
    slot = jnp.minimum(pos, m - 1)
    keep = t[None, :] < lens_new[:, None]

    def row_fn(cc, kc, sp, cn, kn, sl, kp, p_row):
        # clear the slots being written first (as `cache_extend` does), then
        # scatter-add: each slot receives at most one kept position
        hit = jnp.zeros((m,), bool).at[sl].set(kp, mode="drop")
        cc = jnp.where(hit[:, None], jnp.zeros_like(cc), cc)
        kc = jnp.where(hit[:, None], jnp.zeros_like(kc), kc)
        sp = jnp.where(hit, -1, sp)
        cc = cc.at[sl].add(jnp.where(kp[:, None], cn, 0.0))
        kc = kc.at[sl].add(jnp.where(kp[:, None], kn, 0.0))
        sp = sp.at[sl].max(jnp.where(kp, p_row, -1))
        return cc, kc, sp

    return jax.vmap(row_fn)(ckv, krope, slot_pos, ckv_new, krope_new, slot,
                            keep, pos)


def mla_extend(p, x, cache_layer, cfg, lens_new):
    """Multi-turn block extension for MLA latent caches. x: [B,Sn,D]."""
    from repro.models.layers import rms_norm

    b, sn, _ = x.shape
    pos0 = cache_layer["pos"]
    pos = pos0[:, None] + jnp.arange(sn)[None, :]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    q_rope = _mla_rope(q_rope, pos, cfg)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    dkv = jnp.einsum("bsd,dl->bsl", x, p["wdkv"])
    ckv_new, krope_new = jnp.split(dkv, [cfg.kv_lora_rank], axis=-1)
    ckv_new = rms_norm(ckv_new, p["kv_norm"], cfg.norm_eps)
    krope_new = _mla_rope(krope_new[:, :, None, :], pos, cfg)[:, :, 0]

    k_new, v_new = _mla_qkv_from_latent(p, ckv_new, krope_new, cfg)
    k_cache, v_cache = _mla_qkv_from_latent(p, cache_layer["ckv"],
                                            cache_layer["krope"], cfg)
    o = attend_mixed(q, k_new, v_new, k_cache, v_cache,
                     cache_layer["slot_pos"], pos0, lens_new,
                     scale=mla_softmax_scale(cfg))

    ckv_c, kr_c, sp = latent_extend(
        cache_layer["ckv"], cache_layer["krope"], cache_layer["slot_pos"],
        ckv_new, krope_new, pos0, lens_new)
    out = jnp.einsum("bshv,hvd->bsd", o, p["wo"])
    new_cache = {"ckv": ckv_c, "krope": kr_c, "slot_pos": sp, "pos": pos0 + lens_new}
    return out, new_cache
