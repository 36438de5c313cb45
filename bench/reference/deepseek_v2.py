"""Plain reference for DeepSeek-V2 (multi-head latent attention with YaRN
rope, a leading dense layer, then MoE layers of fine-grained routed experts
and shared experts).

A configuration's ``engine.family`` names this file (and
`adapters/deepseek_v2.py`), and the harness takes everything
model-specific from it, keyed by the configuration file's own (Hugging
Face) keys:

* ``make_params``: the weights, made by the benchmark from ``--seed`` in one
  jitted call on the agent's device, in the layout the program's engine
  takes: ``stack0`` the leading dense layers, ``stack1`` the MoE layers,
  each with a stacked layer axis, in the configuration's dtype;
* ``call_counts``: the operations and bytes one forward call needs, from
  its shapes (`counts.served_request` sums them over a served request);
* ``expert_ffn_counts``: the operations and bytes of the routed experts'
  grouped matmul (the program's ``moe_gmm`` kernel) for a number of
  (token, expert) rows over a number of experts;
* ``served_gaps``: the reference forward pass in float32 under
  ``jax.default_matmul_precision("highest")``, layer by layer and in blocks
  of rows, at full width, and the gap by which each served token's logit
  lies below the reference's best at its position.

The forward pass follows the published DeepSeek-V2 block (Hugging Face
``modeling_deepseek.py``): RMSNorm; q projection (no q LoRA); ``kv_a`` to a
``kv_lora_rank`` latent plus one shared ``qk_rope_head_dim`` rope key;
RMSNorm of the latent, then ``kv_b`` to per-head no-rope keys and values;
YaRN rope on q's rope part and on the rope key; causal softmax at scale
``(qk_nope + qk_rope)^-0.5 * mscale(factor, mscale_all_dim)^2``; output
projection, residual; RMSNorm, then a SwiGLU MLP (the first
``first_k_dense_replace`` layers) or the MoE: softmax gate in float32,
greedy top-k, the k weights renormalised only where ``norm_topk_prob``,
times ``routed_scaling_factor``, each picked expert's SwiGLU weighted by
its gate weight, plus the shared experts' SwiGLU; residual; final RMSNorm
and an untied LM head. Each expert is evaluated on every token and weighted
by its gate weight (zero where the gate did not pick it): no grouping, no
capacity. It imports nothing of the program.

One departure: rope acts on the half-split layout (pairs ``i`` and
``i + d/2``). The published code first permutes interleaved pairs into that
layout, so the two are equal up to a fixed permutation of the rope columns
of the q projection and of ``kv_a``'s rope key, which a checkpoint loader
would apply; random weights need none.

``served_gaps(..., controls=("fp8",))`` also reads the control: the same
forward with every projection's weights and inputs (the gate's included)
rounded to float8 e4m3 (per-tensor scale), one precision step below the
configuration's bfloat16. It reads, at each position, the gap of the token
that the control puts first.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _dims(config: dict) -> dict:
    c = config
    return {
        "L": int(c["num_hidden_layers"]), "D": int(c["hidden_size"]),
        "H": int(c["num_attention_heads"]), "lora": int(c["kv_lora_rank"]),
        "nope": int(c["qk_nope_head_dim"]), "rope": int(c["qk_rope_head_dim"]),
        "vd": int(c["v_head_dim"]), "Fd": int(c["intermediate_size"]),
        "F": int(c["moe_intermediate_size"]), "E": int(c["n_routed_experts"]),
        "S": int(c["n_shared_experts"]), "k": int(c["num_experts_per_tok"]),
        "dense": int(c["first_k_dense_replace"]), "V": int(c["vocab_size"]),
    }


def _stacks(d: dict) -> list:
    """(kind, layers) of the program's stacks, in order: stack0, stack1."""
    out = [("dense", d["dense"])] if d["dense"] else []
    return out + [("moe", d["L"] - d["dense"])]


def seed_key(seed: int, index: int):
    """A PRNG key from a seed of any size and an agent index."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, index)


@functools.lru_cache(maxsize=None)
def _param_maker(dims: tuple, dtype: str):
    d = dict(dims)
    D, H, lora, rope = d["D"], d["H"], d["lora"], d["rope"]
    dt = jnp.dtype(dtype)

    def normal(key, shape, fan_in, scale=1.0):
        std = scale / np.sqrt(fan_in)
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    def gain(key, shape):
        # norm gains around 1, so a norm that is skipped shows
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dt)

    def stack(key, kind, n):
        k = jax.random.split(key, 16)
        p = {
            "ln1": gain(k[0], (n, D)),
            "ln2": gain(k[1], (n, D)),
            "attn": {
                "wq": normal(k[2], (n, D, H, d["nope"] + rope), D),
                "wdkv": normal(k[3], (n, D, lora + rope), D),
                "kv_norm": gain(k[4], (n, lora)),
                "wuk": normal(k[5], (n, lora, H, d["nope"]), lora),
                "wuv": normal(k[6], (n, lora, H, d["vd"]), lora),
                "wo": normal(k[7], (n, H, d["vd"], D), H * d["vd"],
                             1.0 / np.sqrt(2 * d["L"])),
            },
        }
        if kind == "dense":
            F = d["Fd"]
            p["mlp"] = {"wg": normal(k[8], (n, D, F), D),
                        "wu": normal(k[9], (n, D, F), D),
                        "wd": normal(k[10], (n, F, D), F)}
        else:
            E, F, SF = d["E"], d["F"], d["S"] * d["F"]
            p["moe"] = {
                "router": normal(k[8], (n, D, E), D),
                "wg": normal(k[9], (n, E, D, F), D),
                "wu": normal(k[10], (n, E, D, F), D),
                "wd": normal(k[11], (n, E, F, D), F),
                "shared": {"wg": normal(k[12], (n, D, SF), D),
                           "wu": normal(k[13], (n, D, SF), D),
                           "wd": normal(k[14], (n, SF, D), SF)},
            }
        return p

    def make(key):
        k = jax.random.split(key, 3 + len(_stacks(d)))
        params = {"embed": normal(k[0], (d["V"], D), 1.0),
                  "final_norm": gain(k[1], (D,)),
                  "lm_head": normal(k[2], (D, d["V"]), D)}
        for i, (kind, n) in enumerate(_stacks(d)):
            params[f"stack{i}"] = stack(k[3 + i], kind, n)
        return params

    return jax.jit(make)


def make_params(config: dict, seed: int, index: int, device=None):
    """Agent ``index``'s weights for ``seed``, made on ``device`` (the
    default device where None) in the configuration's dtype, in one jitted
    call."""
    key = seed_key(seed, index)
    if device is not None:
        key = jax.device_put(key, device)
    dims = tuple(sorted(_dims(config).items()))
    return _param_maker(dims, str(config["torch_dtype"]))(key)


# ---------------- counts ----------------
W = 2  # bytes per weight, activation and cache element (bfloat16)


def expert_ffn_counts(config: dict, rows: int, experts: int) -> tuple:
    """The routed experts' grouped SwiGLU over ``rows`` (token, expert) rows
    that ``experts`` distinct experts computed: (operations, bytes moved at
    the least). Operations: 2 per multiply-add of the three projections for
    each row. Bytes: the weights of each of those experts read once, each
    row's input read and its output written."""
    d = _dims(config)
    ops = 2 * 3 * d["D"] * d["F"] * int(rows)
    nbytes = (int(experts) * 3 * d["D"] * d["F"] + 2 * int(rows) * d["D"]) * W
    return ops, nbytes


def call_counts(config: dict, new_tokens: int, context: int,
                head_rows: int = 1) -> tuple:
    """One forward call: ``new_tokens`` tokens computed after ``context``
    cached ones, logits for ``head_rows`` positions. Returns (operations,
    bytes moved at the least).

    Operations: 2 per multiply-add of every projection for each new token
    (q, ``kv_a``, the output projection, the dense MLP or the gate, the
    ``top_k`` routed and the shared experts), the attention over the causal
    context, and the LM head. The attention is counted in the cheaper of
    MLA's two exact forms: the new tokens' latents expanded by ``kv_b``
    (the cached positions' too) and attended per head, or ``kv_b`` absorbed
    into the query and the output so that attention runs over the latents
    (576 values a position).
    Bytes: every layer's weights read once per call, except that a MoE
    layer reads at least ``top_k`` routed experts (exact for one token, a
    lower bound for more); the LM head; the new tokens' embedding rows; the
    cached latents read and the new ones written (576 values a token a
    layer)."""
    d = _dims(config)
    D, H, lora, nope, rope, vd = (d[n] for n in ("D", "H", "lora", "nope",
                                                 "rope", "vd"))
    t, c = int(new_tokens), int(context)
    seen = t * c + t * (t + 1) // 2        # causal (query, key) pairs
    q_proj = D * H * (nope + rope) + D * (lora + rope) + H * vd * D
    kv_b = lora * H * (nope + vd)
    expanded = (t + c) * kv_b + seen * H * (nope + rope + vd)
    absorbed = t * kv_b + seen * H * (2 * lora + rope)
    attn_ops = 2 * (t * q_proj + min(expanded, absorbed))
    attn_w = q_proj + kv_b + lora + 2 * D
    dense_w = 3 * D * d["Fd"]
    expert = 3 * D * d["F"]
    moe_ops_w = D * d["E"] + (d["k"] + d["S"]) * expert
    moe_w = D * d["E"] + (d["k"] + d["S"]) * expert
    n_dense, n_moe = d["dense"], d["L"] - d["dense"]
    ops = (d["L"] * attn_ops + n_dense * 2 * t * dense_w
           + n_moe * 2 * t * moe_ops_w + 2 * D * d["V"] * head_rows)
    cache = d["L"] * (lora + rope) * W
    nbytes = ((d["L"] * attn_w + n_dense * dense_w + n_moe * moe_w
               + D * d["V"] + D) * W + t * D * W + cache * (c + t))
    return ops, nbytes


# ---------------- the reference forward pass ----------------
def _fp8(x):
    """Round to float8 e4m3 with a per-tensor scale (the control)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = amax / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn(config: dict) -> tuple:
    """(inverse frequencies [rope / 2], cos/sin factor, softmax scale) of
    the configuration's rope, YaRN where ``rope_scaling`` says so."""
    dim, base = int(config["qk_rope_head_dim"]), float(config["rope_theta"])
    inv = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    scale = (int(config["qk_nope_head_dim"]) + dim) ** -0.5
    rs = config.get("rope_scaling")
    if not rs:
        return inv.astype(np.float32), 1.0, scale
    if rs.get("type", rs.get("rope_type")) != "yarn":
        raise ValueError(f"rope scaling {rs!r} is not YaRN")
    f, orig = float(rs["factor"]), int(rs["original_max_position_embeddings"])

    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = inv / f * ramp + inv * (1 - ramp)
    m_all = float(rs.get("mscale_all_dim", 0))
    cs = _yarn_mscale(f, float(rs["mscale"])) / _yarn_mscale(f, m_all)
    if m_all:
        scale *= _yarn_mscale(f, m_all) ** 2
    return inv.astype(np.float32), cs, scale


def _rope(x, pos, inv, cs):
    """Half-split rotary embedding (see the module docstring)."""
    d = x.shape[-1]
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)  # [S, d/2]
    cos = (jnp.cos(ang) * cs)[:, None, :]
    sin = (jnp.sin(ang) * cs)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(mm, h, wg, wu, wd):
    g = jax.nn.silu(mm("bsd,df->bsf", h, wg))
    return mm("bsf,fd->bsd", g * mm("bsd,df->bsf", h, wu), wd)


@functools.lru_cache(maxsize=None)
def _layer_fn(dims: tuple, eps: float, rope: tuple, gate: tuple, kind: str,
              quant: str):
    d = dict(dims)
    inv, cs, scale = np.asarray(rope[0], np.float32), rope[1], rope[2]
    norm_topk_prob, routed_scaling_factor = gate
    q8 = _fp8 if quant == "fp8" else (lambda a: a)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    def mm(spec, a, w):
        return jnp.einsum(spec, q8(a), q8(f32(w)))

    def moe(p, h):
        probs = jax.nn.softmax(mm("bsd,de->bse", h, p["router"]), axis=-1)
        w, idx = jax.lax.top_k(probs, d["k"])
        if norm_topk_prob:
            w = w / w.sum(-1, keepdims=True)
        w = w * routed_scaling_factor
        gate = jnp.sum(jax.nn.one_hot(idx, d["E"]) * w[..., None], axis=-2)

        def expert(acc, xs):
            g_e, wg, wu, wd = xs
            return acc + g_e[..., None] * _swiglu(mm, h, wg, wu, wd), None

        out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                              (jnp.moveaxis(gate, -1, 0), p["wg"], p["wu"],
                               p["wd"]))
        sp = p["shared"]
        return out + _swiglu(mm, h, sp["wg"], sp["wu"], sp["wd"])

    def layer(p, x):
        B, S, _ = x.shape
        a = p["attn"]
        h = _rms(x, f32(p["ln1"]), eps)
        q = mm("bsd,dhk->bshk", h, a["wq"])
        q_nope, q_pe = q[..., : d["nope"]], q[..., d["nope"]:]
        kv = mm("bsd,dl->bsl", h, a["wdkv"])
        lat, k_pe = kv[..., : d["lora"]], kv[..., d["lora"]:]
        lat = _rms(lat, f32(a["kv_norm"]), eps)
        k_nope = mm("bsl,lhn->bshn", lat, a["wuk"])
        v = mm("bsl,lhv->bshv", lat, a["wuv"])
        pos = jnp.arange(S)
        q_pe = _rope(q_pe, pos, inv, cs)
        k_pe = _rope(k_pe[:, :, None, :], pos, inv, cs)
        q = jnp.concatenate([q_nope, q_pe], -1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (B, S, d["H"], d["rope"]))], -1)
        s = jnp.einsum("bshk,bthk->bhst", q, k) * scale
        s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
        o = jnp.einsum("bhst,bthv->bshv", jax.nn.softmax(s, axis=-1), v)
        x = x + mm("bshv,hvd->bsd", o, a["wo"])
        h = _rms(x, f32(p["ln2"]), eps)
        if kind == "dense":
            return x + _swiglu(mm, h, p["mlp"]["wg"], p["mlp"]["wu"],
                               p["mlp"]["wd"])
        return x + moe(p["moe"], h)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, quant: str):
    q8 = _fp8 if quant == "fp8" else (lambda a: a)

    def head(final_norm, lm_head, x, rows, cols):
        h = _rms(x[rows, cols], final_norm.astype(jnp.float32), eps)
        return jnp.einsum("nd,dv->nv", q8(h), q8(lm_head.astype(jnp.float32)))

    return jax.jit(head)


def _bucket(n: int, lo: int = 64) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def reference_logits(params, config: dict, seqs: list, reads: list,
                     quant: str = "none", block: int = 8) -> list:
    """float32 logits at positions ``reads[i]`` of each sequence ``seqs[i]``
    (the logits there predict the token after that position)."""
    d = _dims(config)
    inv, cs, scale = yarn(config)
    key = (tuple(sorted(d.items())), float(config["rms_norm_eps"]),
           (tuple(inv.tolist()), cs, scale),
           (bool(config["norm_topk_prob"]),
            float(config["routed_scaling_factor"])))
    stacks = [(f"stack{i}", _layer_fn(*key, kind, quant), n)
              for i, (kind, n) in enumerate(_stacks(d))]
    head = _head_fn(float(config["rms_norm_eps"]), quant)
    out = [None] * len(seqs)
    order = sorted(range(len(seqs)), key=lambda i: len(seqs[i]))
    with jax.default_matmul_precision("highest"):
        for b0 in range(0, len(order), block):
            idx = order[b0: b0 + block]
            S = _bucket(max(len(seqs[i]) for i in idx))
            toks = np.zeros((block, S), np.int32)
            for r, i in enumerate(idx):
                toks[r, : len(seqs[i])] = seqs[i]
            x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
            for name, layer, n in stacks:
                for l in range(n):
                    x = layer(jax.tree.map(lambda w: w[l], params[name]), x)
            rows = np.concatenate([np.full(len(reads[i]), r, np.int32)
                                   for r, i in enumerate(idx)])
            cols = np.concatenate([np.asarray(reads[i], np.int32)
                                   for i in idx])
            logits = np.asarray(head(params["final_norm"], params["lm_head"],
                                     x, jnp.asarray(rows), jnp.asarray(cols)))
            k = 0
            for i in idx:
                out[i] = logits[k: k + len(reads[i])]
                k += len(reads[i])
    return out


def served_gaps(config: dict, seed: int, samples: dict,
                controls: tuple = ()) -> dict:
    """Widest gaps, over every served token of the sampled requests.

    ``samples`` maps an agent index to its list of ``(prompt, output)``
    token arrays. For each position that produced a served token, the gap is
    the reference's best logit minus the reference's logit of that token.
    ``controls`` names reduced-precision forwards (``"fp8"``) whose own
    first-choice tokens are read at the same positions. Returns
    ``{"program": gap, "<control>": gap, "tokens": n}``.
    """
    worst = {"program": 0.0, **{c: 0.0 for c in controls}}
    n_tok = 0
    for index, pairs in samples.items():
        if not pairs:
            continue
        params = make_params(config, seed, index)
        seqs = [np.concatenate([p, o[:-1]]) for p, o in pairs]
        reads = [np.arange(len(p) - 1, len(p) - 1 + len(o)) for p, o in pairs]
        ref = reference_logits(params, config, seqs, reads)
        for (p, o), lg in zip(pairs, ref):
            gap = lg.max(axis=1) - lg[np.arange(len(o)), o]
            worst["program"] = max(worst["program"], float(gap.max()))
            n_tok += len(o)
        for c in controls:
            ctl = reference_logits(params, config, seqs, reads, quant=c)
            for lg, lc in zip(ref, ctl):
                pick = lc.argmax(axis=1)
                gap = lg.max(axis=1) - lg[np.arange(len(pick)), pick]
                worst[c] = max(worst[c], float(gap.max()))
        del params
    worst["tokens"] = n_tok
    return worst
