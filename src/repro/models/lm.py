"""Decoder-only LM driver for all assigned architecture families.

Design notes
------------
* Homogeneous layers are STACKED (leading dim L) and driven by ``lax.scan``
  so compile time is O(1) in depth (DESIGN.md §6). Heterogeneous archs are a
  short list of homogeneous stacks (deepseek: 1 dense + 26 MoE) or a grouped
  structure (zamba2: 13 x [6 mamba + shared attn] + 3 mamba).
* ``extend`` is the multi-turn entry point the serving engine uses for
  KV-prefix reuse — the physical substrate of the paper's affinity o_ij.
* Training uses jax.checkpoint around each block (scan-over-layers remat).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.models import attention as attn
from repro.models import blocks as blk
from repro.models.scan_config import indexed_layer_loop, layer_scan
from repro.models.layers import next_token_loss, normal_init, rms_norm


@dataclass(frozen=True)
class StackSpec:
    n_layers: int
    ffn_kind: str  # dense | moe
    d_ff: int


def _make_stacks(cfg) -> list[StackSpec]:
    if cfg.is_moe:
        nd = cfg.first_dense_layers
        stacks = []
        if nd:
            stacks.append(StackSpec(nd, "dense", cfg.d_ff))
        stacks.append(StackSpec(cfg.n_layers - nd, "moe", cfg.moe_d_ff or cfg.d_ff))
        return stacks
    return [StackSpec(cfg.n_layers, "dense", cfg.d_ff)]


def _zamba_groups(cfg):
    g = cfg.n_layers // cfg.attn_every
    tail = cfg.n_layers - g * cfg.attn_every
    return g, cfg.attn_every, tail


def build_lm(cfg):
    dtype = jnp.dtype(cfg.dtype)
    family = ("rwkv" if cfg.ssm_kind == "rwkv6"
              else "zamba" if cfg.attn_every
              else "attn")
    stacks = _make_stacks(cfg) if family == "attn" else []

    # ---------------- init ----------------
    def init(key):
        keys = jax.random.split(key, 8)
        params = {
            "embed": normal_init(keys[0], (cfg.vocab_size, cfg.d_model),
                                 cfg.d_model, dtype),
            "final_norm": jnp.ones((cfg.d_model,), dtype),
            "lm_head": normal_init(keys[1], (cfg.d_model, cfg.vocab_size),
                                   cfg.d_model, dtype),
        }
        if family == "attn":
            import dataclasses as dc
            for i, spec in enumerate(stacks):
                sub = dc.replace(cfg, d_ff=spec.d_ff)
                lkeys = jax.random.split(jax.random.fold_in(keys[2], i), spec.n_layers)
                params[f"stack{i}"] = jax.vmap(
                    lambda k: blk.attn_block_init(k, sub, dtype, ffn_kind=spec.ffn_kind)
                )(lkeys)
        elif family == "rwkv":
            lkeys = jax.random.split(keys[2], cfg.n_layers)
            params["layers"] = jax.vmap(
                lambda k: blk.rwkv_block_init(k, cfg, dtype))(lkeys)
        else:  # zamba
            g, per, tail = _zamba_groups(cfg)
            gkeys = jax.random.split(keys[2], g * per).reshape(g, per, -1)
            params["groups"] = jax.vmap(jax.vmap(
                lambda k: blk.mamba_block_init(k, cfg, dtype)))(gkeys)
            if tail:
                tkeys = jax.random.split(keys[3], tail)
                params["tail"] = jax.vmap(
                    lambda k: blk.mamba_block_init(k, cfg, dtype))(tkeys)
            params["shared"] = blk.shared_attn_init(keys[4], cfg, dtype, g)
        return params

    def param_axes():
        ax = {"embed": "vocab embed", "final_norm": "embed",
              "lm_head": "embed vocab"}
        if family == "attn":
            import dataclasses as dc
            for i, spec in enumerate(stacks):
                sub = dc.replace(cfg, d_ff=spec.d_ff)
                ax[f"stack{i}"] = _prefix_axes(
                    blk.attn_block_axes(sub, ffn_kind=spec.ffn_kind), "layers")
        elif family == "rwkv":
            ax["layers"] = _prefix_axes(blk.rwkv_block_axes(cfg), "layers")
        else:
            ax["groups"] = _prefix_axes(_prefix_axes(blk.mamba_block_axes(cfg),
                                                     "layers"), "groups")
            g, per, tail = _zamba_groups(cfg)
            if tail:
                ax["tail"] = _prefix_axes(blk.mamba_block_axes(cfg), "layers")
            ax["shared"] = blk.shared_attn_axes(cfg)
        return ax

    # ---------------- embedding / head ----------------
    def _embed_inputs(params, batch):
        tokens = batch["tokens"]
        x = params["embed"][tokens]
        if cfg.n_patches and "patches" in batch:
            patches = batch["patches"].astype(x.dtype)
            x = jnp.concatenate([patches, x], axis=1)
        x = shard(x, "batch", "seq", "embed")
        return x

    def _head(params, x):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("...d,dv->...v", x, params["lm_head"])
        return logits

    # ---------------- parallel forward (train / fresh prefill) ----------
    def forward(params, batch, *, remat: bool, collect: bool, lens=None,
                init_state=None):
        """Returns (x_final, cache_parts dict or None)."""
        x = _embed_inputs(params, batch)
        parts = {}
        if family == "attn":
            stats = []
            for i, spec in enumerate(stacks):
                sliced, experts = _unstack_experts(params[f"stack{i}"])

                def body(carry, xs, _spec=spec, _experts=experts):
                    p_l, l = xs
                    y, kv, st = blk.attn_block_parallel(
                        _with_experts(p_l, _experts), carry, cfg,
                        ffn_kind=_spec.ffn_kind, lens=lens,
                        layer=None if _experts is None else l)
                    return y, (kv if collect else None, st)
                f = jax.checkpoint(body) if remat else body
                x, (kvs, st) = layer_scan(
                    f, x, (sliced, jnp.arange(spec.n_layers)))
                if collect:
                    parts[f"stack{i}"] = kvs
                if st is not None:
                    stats.append(st.sum(axis=0))
            if stats:
                parts["moe_stats"] = sum(stats)
        elif family == "rwkv":
            def body(carry, xs):
                p_l, st = xs
                y, new_st = blk.rwkv_block_parallel(p_l, carry, cfg, state=st)
                return y, (new_st if collect else None)
            b = x.shape[0]
            st0 = init_state if init_state is not None else _rwkv_zero_state(
                cfg, cfg.n_layers, b, x.dtype)
            f = jax.checkpoint(body) if remat else body
            x, sts = layer_scan(f, x, (params["layers"], st0))
            if collect:
                parts["states"] = sts
        else:  # zamba
            g, per, tail = _zamba_groups(cfg)
            b = x.shape[0]
            st = init_state if init_state is not None else _zamba_zero_state(
                cfg, b, x.dtype)

            def group_body(carry, xs):
                p_g, lora_g, st_g = xs

                def inner(c, xs2):
                    p_l, st_l = xs2
                    y, new_st = blk.mamba_block_parallel(p_l, c, cfg, state=st_l)
                    return y, (new_st if collect else None)

                y, mstates = layer_scan(inner, carry, (p_g, st_g))
                y, kv = blk.shared_attn_parallel(params["shared"], lora_g, y,
                                                 cfg, lens=lens)
                return y, ((mstates, kv) if collect else None)

            f = jax.checkpoint(group_body) if remat else group_body
            x, gouts = layer_scan(f, x, (params["groups"],
                                           params["shared"]["lora"],
                                           st["groups"]))
            if collect:
                parts["groups"] = gouts
            if tail:
                def tbody(c, xs2):
                    p_l, st_l = xs2
                    y, new_st = blk.mamba_block_parallel(p_l, c, cfg, state=st_l)
                    return y, (new_st if collect else None)
                ft = jax.checkpoint(tbody) if remat else tbody
                x, touts = layer_scan(ft, x, (params["tail"], st["tail"]))
                if collect:
                    parts["tail"] = touts
        return x, parts

    # ---------------- loss ----------------
    def loss(params, batch):
        x, _ = forward(params, batch, remat=True, collect=False)
        if cfg.n_patches:
            targets = jnp.concatenate(
                [jnp.full((batch["tokens"].shape[0], cfg.n_patches), -100,
                          batch["tokens"].dtype), batch["tokens"]], axis=1)
        else:
            targets = batch["tokens"]
        logits = _head(params, x)
        logits = shard(logits, "batch", "logit_seq", "vocab")
        return next_token_loss(logits, targets)

    # ---------------- caches ----------------
    # A MoE model's cache also carries ``moe_stats``: the int32[2] (rows,
    # experts) its routed experts computed, summed over layers, in the call
    # that returned the cache (a prefill, an extend or one decode step).
    def init_cache(b: int, max_len: int):
        m = min(cfg.sliding_window, max_len) if cfg.sliding_window else max_len
        pos = jnp.zeros((b,), jnp.int32)
        if family == "attn":
            c = {"pos": pos}
            for i, spec in enumerate(stacks):
                c[f"stack{i}"] = _attn_stack_cache(cfg, spec, b, m, dtype)
            c["slot_pos"] = jnp.full((b, m), -1, jnp.int32)
            if cfg.is_moe:
                c["moe_stats"] = jnp.zeros((2,), jnp.int32)
            return c
        if family == "rwkv":
            return {"pos": pos,
                    "states": _rwkv_zero_state(cfg, cfg.n_layers, b, dtype)}
        g, per, tail = _zamba_groups(cfg)
        c = {"pos": pos, "slot_pos": jnp.full((b, m), -1, jnp.int32),
             "mamba": _zamba_zero_state(cfg, b, dtype),
             "attn_k": jnp.zeros((g, b, m, cfg.n_kv_heads, cfg.hd), dtype),
             "attn_v": jnp.zeros((g, b, m, cfg.n_kv_heads, cfg.hd), dtype)}
        return c

    # ---------------- fresh prefill ----------------
    def prefill(params, batch):
        """batch: tokens [B,S] (+lens [B] for right-padded attn archs).
        Returns (last-token logits [B,V], cache)."""
        tokens = batch["tokens"]
        b, s = tokens.shape
        lens = batch.get("lens", jnp.full((b,), s, jnp.int32))
        if cfg.n_patches and "patches" in batch:
            lens = lens + cfg.n_patches
            s = s + cfg.n_patches
        max_len = int(batch.get("max_len", s))
        x, parts = forward(params, batch, remat=False, collect=True, lens=lens)
        x_last = jnp.take_along_axis(
            x, jnp.maximum(lens - 1, 0)[:, None, None], axis=1)[:, 0]
        logits = _head(params, x_last)
        logits = shard(logits, "batch", "vocab")

        cache = init_cache(b, max_len)
        cache["pos"] = lens
        if "moe_stats" in parts:
            cache["moe_stats"] = parts["moe_stats"]
        if family == "attn":
            m = cache["slot_pos"].shape[1]
            for i, spec in enumerate(stacks):
                kvs = parts[f"stack{i}"]
                if cfg.attn_kind == "mla":
                    ckv, krope = kvs  # [L,B,S,lora], [L,B,S,rope]
                    # masked pad, not scatter (keeps seq sharding; §Perf)
                    take = lambda c_: jnp.pad(
                        c_, ((0, 0), (0, 0), (0, m - s), (0, 0)))
                    cache[f"stack{i}"]["ckv"] = take(ckv)
                    cache[f"stack{i}"]["krope"] = take(krope)
                    valid = jnp.arange(s)[None, :] < lens[:, None]
                    sp = jnp.pad(jnp.where(valid, jnp.arange(s)[None, :], -1),
                                 ((0, 0), (0, m - s)), constant_values=-1)
                    cache["slot_pos"] = sp.astype(jnp.int32)
                else:
                    k_l, v_l = kvs  # [L,B,S,Hkv,hd]
                    lay = jax.vmap(lambda kk, vv: attn.prefill_cache_layout(
                        kk, vv, lens, max_len, window=cfg.sliding_window))
                    kc, vc, sp = lay(k_l, v_l)
                    cache[f"stack{i}"]["k"] = kc
                    cache[f"stack{i}"]["v"] = vc
                    cache["slot_pos"] = sp[0]
        elif family == "rwkv":
            cache["states"] = parts["states"]
        else:
            mstates, kvs = parts["groups"]
            cache["mamba"]["groups"] = mstates
            if "tail" in parts:
                cache["mamba"]["tail"] = parts["tail"]
            k_g, v_g = kvs  # [G,B,S,Hkv,hd]
            m = cache["slot_pos"].shape[1]
            lay = jax.vmap(lambda kk, vv: attn.prefill_cache_layout(
                kk, vv, lens, max_len))
            kc, vc, sp = lay(k_g, v_g)
            cache["attn_k"], cache["attn_v"] = kc, vc
            cache["slot_pos"] = sp[0]
        return logits, cache

    # ---------------- decode step ----------------
    # Decode iterates layers with jax.lax.fori_loop carrying the FULL cache:
    # each layer's update is an in-place dynamic-update-slice on the carry,
    # so the cache is single-buffered (a scan's xs/ys would double-buffer
    # multi-GB caches; measured in EXPERIMENTS.md §Perf).
    def _slice_l(tree, l):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, l, 0, keepdims=False),
            tree)

    def _put_l(tree, upd, l):
        return jax.tree.map(
            lambda a, u: jax.lax.dynamic_update_index_in_dim(a, u, l, 0),
            tree, upd)

    def decode_step(params, cache, tokens):
        """tokens: [B] -> (logits [B,V], new cache)."""
        x = params["embed"][tokens]
        x = shard(x, "batch", "embed")
        pos = cache["pos"]
        new_cache = dict(cache)
        if family == "attn":
            sp_out = cache["slot_pos"]
            work = jnp.zeros((2,), jnp.int32) if cfg.is_moe else None
            for i, spec in enumerate(stacks):
                st_cache = cache[f"stack{i}"]
                pstack, experts = _unstack_experts(params[f"stack{i}"])
                keys = ("ckv", "krope") if cfg.attn_kind == "mla" else ("k", "v")

                def body(l, carry, _spec=spec, _pstack=pstack, _keys=keys,
                         _experts=experts):
                    y, st, sp, w = carry
                    p_l = _with_experts(_slice_l(_pstack, l), _experts)
                    cl = dict(zip(_keys, (_slice_l(st[kk], l) for kk in _keys)))
                    cl.update(slot_pos=cache["slot_pos"], pos=pos)
                    y, nc, stats = blk.attn_block_decode(
                        p_l, y, cl, cfg, ffn_kind=_spec.ffn_kind,
                        layer=None if _experts is None else l)
                    st = {kk: _put_l(st[kk], nc[kk], l) for kk in _keys}
                    return (y, st, nc["slot_pos"],
                            w if stats is None else w + stats)

                x, st_new, sp_out, work = indexed_layer_loop(
                    spec.n_layers, body, (x, dict(st_cache), sp_out, work))
                new_cache[f"stack{i}"] = st_new
            new_cache["slot_pos"] = sp_out
            if cfg.is_moe:
                new_cache["moe_stats"] = work
        elif family == "rwkv":
            def body(l, carry):
                y, states = carry
                p_l = _slice_l(params["layers"], l)
                st_l = _slice_l(states, l)
                y, new_st = blk.rwkv_block_step(p_l, y, cfg, st_l)
                return (y, _put_l(states, new_st, l))

            x, sts = indexed_layer_loop(cfg.n_layers, body,
                                        (x, cache["states"]))
            new_cache["states"] = sts
        else:  # zamba
            g, per, tail = _zamba_groups(cfg)

            def group_body(gi, carry):
                y, mst, kc, vc, sp = carry
                p_g = _slice_l(params["groups"], gi)
                lora_g = _slice_l(params["shared"]["lora"], gi)
                st_g = _slice_l(mst, gi)

                def inner(c, xs2):
                    p_l, st_l = xs2
                    z, new_st = blk.mamba_block_step(p_l, c, cfg, st_l)
                    return z, new_st

                y, mstates = layer_scan(inner, y, (p_g, st_g))
                cl = {"k": _slice_l(kc, gi), "v": _slice_l(vc, gi),
                      "slot_pos": cache["slot_pos"], "pos": pos}
                y, nc = blk.shared_attn_decode(params["shared"], lora_g, y,
                                               cl, cfg)
                return (y, _put_l(mst, mstates, gi),
                        _put_l(kc, nc["k"], gi), _put_l(vc, nc["v"], gi),
                        nc["slot_pos"])

            x, mstates, k_n, v_n, sp_n = indexed_layer_loop(
                g, group_body,
                (x, cache["mamba"]["groups"], cache["attn_k"],
                 cache["attn_v"], cache["slot_pos"]))
            new_cache["mamba"] = dict(cache["mamba"])
            new_cache["mamba"]["groups"] = mstates
            new_cache["attn_k"], new_cache["attn_v"] = k_n, v_n
            new_cache["slot_pos"] = sp_n
            if tail:
                def tbody(l, carry):
                    y, states = carry
                    p_l = _slice_l(params["tail"], l)
                    st_l = _slice_l(states, l)
                    y, new_st = blk.mamba_block_step(p_l, y, cfg, st_l)
                    return (y, _put_l(states, new_st, l))
                x, tst = indexed_layer_loop(tail, tbody,
                                            (x, cache["mamba"]["tail"]))
                new_cache["mamba"]["tail"] = tst
        new_cache["pos"] = pos + 1
        logits = _head(params, x)
        logits = shard(logits, "batch", "vocab")
        return logits, new_cache

    # ---------------- multi-turn extend (serving KV reuse) ----------------
    def extend(params, cache, tokens, lens_new):
        """Process a new block of tokens against an existing cache.

        tokens: [B, Sn]; lens_new: [B]. For attention archs this is chunked
        prefill over the KV cache; for recurrent archs it is a parallel run
        from the stored state (exact-extension semantics, DESIGN.md §4).
        """
        x = params["embed"][tokens]
        pos0 = cache["pos"]
        new_cache = dict(cache)
        if family == "attn":
            sp_out = cache["slot_pos"]
            valid = jnp.arange(tokens.shape[1])[None, :] < lens_new[:, None]
            stats = []
            for i, spec in enumerate(stacks):
                st_cache = cache[f"stack{i}"]
                sliced, experts = _unstack_experts(params[f"stack{i}"])
                layers = jnp.arange(spec.n_layers)
                if cfg.attn_kind == "mla":
                    def body(carry, xs, _spec=spec, _experts=experts):
                        p_l, ckv_l, kr_l, l = xs
                        p_l = _with_experts(p_l, _experts)
                        with jax.named_scope(blk.ATTN_SCOPE):
                            h = rms_norm(carry, p_l["ln1"], cfg.norm_eps)
                            cl = {"ckv": ckv_l, "krope": kr_l,
                                  "slot_pos": cache["slot_pos"], "pos": pos0}
                            o, nc = attn.mla_extend(p_l["attn"], h, cl, cfg,
                                                    lens_new)
                            y = carry + o
                        y, st = blk.block_ffn(
                            p_l, y, cfg, _spec.ffn_kind, valid,
                            None if _experts is None else l)
                        return y, (nc["ckv"], nc["krope"], nc["slot_pos"], st)
                    x, (ckv_n, kr_n, sp_n, st) = layer_scan(
                        body, x, (sliced, st_cache["ckv"], st_cache["krope"],
                                  layers))
                    new_cache[f"stack{i}"] = {"ckv": ckv_n, "krope": kr_n}
                    sp_out = sp_n[0]
                else:
                    def body(carry, xs, _spec=spec, _experts=experts):
                        p_l, k_l, v_l, l = xs
                        p_l = _with_experts(p_l, _experts)
                        with jax.named_scope(blk.ATTN_SCOPE):
                            h = rms_norm(carry, p_l["ln1"], cfg.norm_eps)
                            cl = {"k": k_l, "v": v_l,
                                  "slot_pos": cache["slot_pos"], "pos": pos0}
                            o, nc = attn.gqa_extend(p_l["attn"], h, cl, cfg,
                                                    lens_new)
                            y = carry + o
                        y, st = blk.block_ffn(
                            p_l, y, cfg, _spec.ffn_kind, valid,
                            None if _experts is None else l)
                        return y, (nc["k"], nc["v"], nc["slot_pos"], st)
                    x, (k_n, v_n, sp_n, st) = layer_scan(
                        body, x, (sliced, st_cache["k"], st_cache["v"],
                                  layers))
                    new_cache[f"stack{i}"] = {"k": k_n, "v": v_n}
                    sp_out = sp_n[0]
                if st is not None:
                    stats.append(st.sum(axis=0))
            new_cache["slot_pos"] = sp_out
            if stats:
                new_cache["moe_stats"] = sum(stats)
        elif family == "rwkv":
            batch = {"tokens": tokens}
            x, parts = forward(params, batch, remat=False, collect=True,
                               init_state=cache["states"])
            new_cache["states"] = parts["states"]
        else:
            raise NotImplementedError(
                "zamba2 extend: use prefill from scratch (engine falls back)")
        new_cache["pos"] = pos0 + lens_new
        x_last = jnp.take_along_axis(
            x, jnp.maximum(lens_new - 1, 0)[:, None, None], axis=1)[:, 0]
        logits = _head(params, x_last)
        return logits, new_cache

    return {
        "init": init, "param_axes": param_axes, "loss": loss,
        "prefill": prefill, "decode_step": decode_step, "extend": extend,
        "init_cache": init_cache, "family": family,
    }


def _unstack_experts(pstack):
    """A stack's params to slice layer by layer, and a MoE stack's routed
    expert weights kept whole (None for a dense stack): the grouped expert
    kernel reads one layer of them in place, where a slice would copy the
    layer's experts on every call."""
    from repro.models.moe import EXPERT_WEIGHTS

    if "moe" not in pstack:
        return pstack, None
    moe = pstack["moe"]
    rest = {k: v for k, v in moe.items() if k not in EXPERT_WEIGHTS}
    return {**pstack, "moe": rest}, {k: moe[k] for k in EXPERT_WEIGHTS}


def _with_experts(p_l, experts):
    """One layer's params with the stack's whole expert weights put back."""
    if experts is None:
        return p_l
    return {**p_l, "moe": {**p_l["moe"], **experts}}


def _attn_stack_cache(cfg, spec, b, m, dtype):
    """Per-stack KV cache arrays (leading dim = layers in the stack)."""
    l = spec.n_layers
    if cfg.attn_kind == "mla":
        return {"ckv": jnp.zeros((l, b, m, cfg.kv_lora_rank), dtype),
                "krope": jnp.zeros((l, b, m, cfg.qk_rope_dim), dtype)}
    return {"k": jnp.zeros((l, b, m, cfg.n_kv_heads, cfg.hd), dtype),
            "v": jnp.zeros((l, b, m, cfg.n_kv_heads, cfg.hd), dtype)}


def _prefix_axes(ax, name: str):
    return jax.tree.map(lambda s: f"{name} {s}", ax)


def _rwkv_zero_state(cfg, n_layers, b, dtype):
    h, hd = cfg.ssm_heads, cfg.ssm_state
    return (jnp.zeros((n_layers, b, cfg.d_model), dtype),
            jnp.zeros((n_layers, b, h, hd, hd), jnp.float32),
            jnp.zeros((n_layers, b, cfg.d_model), dtype))


def _zamba_zero_state(cfg, b, dtype):
    g = cfg.n_layers // cfg.attn_every
    per = cfg.attn_every
    tail = cfg.n_layers - g * per
    di = 2 * cfg.d_model
    h, hd, ds = cfg.ssm_heads, (2 * cfg.d_model) // cfg.ssm_heads, cfg.ssm_state
    mk = lambda *lead: (jnp.zeros((*lead, b, 3, di), dtype),
                        jnp.zeros((*lead, b, h, hd, ds), jnp.float32))
    st = {"groups": mk(g, per)}
    if tail:
        st["tail"] = mk(tail)
    return st
