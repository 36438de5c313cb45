"""Plain reference for the dense GQA decoder with per-head qk-norm (Qwen3).

A configuration's ``engine.family`` names this file, and the harness takes
everything model-specific from it, keyed by the configuration file's own
(Hugging Face) keys:

* ``make_params``: the weights, made by the benchmark from ``--seed`` in one
  jitted call on the agent's device, in the layout the program's engine
  takes (a stacked layer axis), in the configuration's dtype;
* ``call_counts``: the operations and bytes one forward call needs, from
  its shapes (`counts.served_request` sums them over a served request);
* ``served_gaps``: the reference forward pass in float32 under
  ``jax.default_matmul_precision("highest")``, layer by layer and in blocks
  of rows, at full width, and the gap by which each served token's logit
  lies below the reference's best at its position.

A second family is a second file beside this one with the same three
functions, and a file `adapters/<family>.py` with the program's
``program_config``.

The forward pass follows the published Qwen3 block: RMSNorm, q/k/v
projections, RMSNorm over each head of q and k, rotary embedding (half
rotation, theta from the config), causal grouped-query attention, output
projection, residual; RMSNorm, SwiGLU MLP, residual; final RMSNorm and an
untied LM head. It imports nothing of the program.

``served_gaps(..., controls=("fp8",))`` also reads the control: the same forward with
every projection's weights and inputs rounded to float8 e4m3 (per-tensor
scale), one precision step below the configuration's bfloat16. It reads, at
each position, the gap of the token that the control puts first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _dims(config: dict) -> tuple:
    return (int(config["num_hidden_layers"]), int(config["hidden_size"]),
            int(config["num_attention_heads"]),
            int(config["num_key_value_heads"]), int(config["head_dim"]),
            int(config["intermediate_size"]), int(config["vocab_size"]))


def seed_key(seed: int, index: int):
    """A PRNG key from a seed of any size and an agent index."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, index)


@functools.lru_cache(maxsize=None)
def _param_maker(dims: tuple, dtype: str):
    L, D, H, K, hd, F, V = dims
    dt = jnp.dtype(dtype)

    def normal(key, shape, fan_in, scale=1.0):
        std = scale / np.sqrt(fan_in)
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    def gain(key, shape):
        # norm gains around 1, so a norm that is skipped shows
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dt)

    def make(key):
        k = jax.random.split(key, 13)
        return {
            "embed": normal(k[0], (V, D), 1.0),
            "final_norm": gain(k[1], (D,)),
            "lm_head": normal(k[2], (D, V), D),
            "stack0": {
                "ln1": gain(k[3], (L, D)),
                "ln2": gain(k[4], (L, D)),
                "attn": {
                    "wq": normal(k[5], (L, D, H, hd), D),
                    "wk": normal(k[6], (L, D, K, hd), D),
                    "wv": normal(k[7], (L, D, K, hd), D),
                    "wo": normal(k[8], (L, H, hd, D), H * hd,
                                 1.0 / np.sqrt(2 * L)),
                    "q_norm": gain(k[9], (L, hd)),
                    "k_norm": gain(k[10], (L, hd)),
                },
                "mlp": {
                    "wg": normal(k[11], (L, D, F), D),
                    "wu": normal(jax.random.fold_in(k[11], 1), (L, D, F), D),
                    "wd": normal(k[12], (L, F, D), F),
                },
            },
        }

    return jax.jit(make)


def make_params(config: dict, seed: int, index: int, device=None):
    """Agent ``index``'s weights for ``seed``, made on ``device`` (the
    default device where None) in the configuration's dtype, in one jitted
    call."""
    key = seed_key(seed, index)
    if device is not None:
        key = jax.device_put(key, device)
    return _param_maker(_dims(config), str(config["torch_dtype"]))(key)


def call_counts(config: dict, new_tokens: int, context: int,
                head_rows: int = 1) -> tuple:
    """One forward call of the dense decoder: ``new_tokens`` tokens computed
    after ``context`` cached ones, logits for ``head_rows`` positions.
    Returns (operations, bytes moved at the least).

    Operations: 2 per multiply-add of every projection for each new token,
    the attention scores and mixing over the causal context, and the LM head.
    Bytes: every layer's weights and the LM head read once per call (bf16,
    the configuration's dtype), the new tokens' embedding rows, the cached
    keys and values read, and the new ones written."""
    L, D, H, K, hd, F, V = _dims(config)
    w = 2  # bytes per weight and cache element (bfloat16)
    t, c = int(new_tokens), int(context)
    proj = D * (H + 2 * K) * hd + H * hd * D + 3 * D * F
    # causal: token k of the block sees c + k + 1 positions
    seen = t * c + t * (t + 1) // 2
    ops = L * (2 * proj * t + 4 * H * hd * seen) + 2 * D * V * head_rows
    layer_w = proj + 2 * D + 2 * hd
    kv_read = L * 2 * K * hd * c * w
    kv_write = L * 2 * K * hd * t * w
    nbytes = (L * layer_w + D * V + D) * w + t * D * w + kv_read + kv_write
    return ops, nbytes


# ---------------- the reference forward pass ----------------
def _fp8(x):
    """Round to float8 e4m3 with a per-tensor scale (the control)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = amax / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * freqs          # [S, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.lru_cache(maxsize=None)
def _layer_fn(eps: float, theta: float, quant: str):
    q8 = _fp8 if quant == "fp8" else (lambda a: a)

    def mm(spec, a, w):
        return jnp.einsum(spec, q8(a), q8(w.astype(jnp.float32)))

    def layer(p, x):
        B, S, D = x.shape
        H, hd = p["wq"].shape[1], p["wq"].shape[2]
        K = p["wk"].shape[1]
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        h = _rms(x, f32(p["ln1"]), eps)
        q = mm("bsd,dhk->bshk", h, p["wq"])
        k = mm("bsd,dhk->bshk", h, p["wk"])
        v = mm("bsd,dhk->bshk", h, p["wv"])
        q = _rms(q, f32(p["q_norm"]), eps)
        k = _rms(k, f32(p["k_norm"]), eps)
        pos = jnp.arange(S)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        qg = q.reshape(B, S, K, H // K, hd)
        s = jnp.einsum("bskgd,btkd->bkgst", qg, k) / np.sqrt(hd)
        causal = pos[None, :] <= pos[:, None]
        s = jnp.where(causal, s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgst,btkd->bskgd", a, v).reshape(B, S, H, hd)
        x = x + mm("bshk,hkd->bsd", o, p["wo"])
        h = _rms(x, f32(p["ln2"]), eps)
        g = jax.nn.silu(mm("bsd,df->bsf", h, p["wg"]))
        u = mm("bsd,df->bsf", h, p["wu"])
        return x + mm("bsf,fd->bsd", g * u, p["wd"])

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
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    L = int(config["num_hidden_layers"])
    layer, head = _layer_fn(eps, theta, quant), _head_fn(eps, quant)
    stack = params["stack0"]
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
            for l in range(L):
                p = {"ln1": stack["ln1"][l], "ln2": stack["ln2"][l],
                     **{n: w[l] for n, w in stack["attn"].items()},
                     **{n: w[l] for n, w in stack["mlp"].items()}}
                x = layer(p, x)
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
