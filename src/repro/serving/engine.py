"""Per-agent inference engine: REAL JAX prefill/extend/decode with KV reuse.

Each serving agent runs a reduced JAX model (configs/iemas_cluster.py). The
engine keeps per-dialogue caches (LRU over ``cache_slots`` sessions — the
paper's constrained-memory / frequent-eviction regime) and measures:

  * TTFT       — wall-clock seconds of the prefill/extend path (real compute,
                 scaled by the agent's hardware ``speed``),
  * n_hit      — exactly how many prompt tokens were served from cache
                 (whole-prefix reuse for attention archs with truncation to
                 the LCP; exact-extension for recurrent archs),
  * n_gen      — generated tokens.

This gives the paper's causal chain *physically*: routing with affinity ->
more cached tokens -> less prefill compute -> lower TTFT and cost.

Prompt lengths are bucketed (powers of two) so jit caches stay small. The
greedy decode loop is one device program per request (`_greedy`), its
tokens read back once.
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.affinity import lcp_length
from repro.models import build_model
from repro.utils.timing import phase_scope


def _bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


@dataclass
class SessionCache:
    """One dialogue's cached model state + the prompt it encodes."""

    cache: object             # model cache pytree (B=1)
    prompt: np.ndarray        # tokens whose state the cache encodes
    last_used: float = 0.0


@dataclass
class ServeResult:
    """Measured outcome of one request: tokens, timings, cache accounting."""

    output_tokens: np.ndarray
    ttft: float               # seconds (scaled by agent speed)
    total_time: float
    n_prompt: int
    n_hit: int
    n_gen: int


# Engines of the same model class share one Model + jit cache: params are
# same-shaped arguments, so XLA compiles each shape bucket ONCE per class
# across the whole cluster (keeps CPU compile time out of TTFT measurements).
_SHARED: dict = {}


#: the cache entry in which a MoE model's calls return their expert work
#: (int32 [rows, experts] of the call; `repro.models.lm`)
MOE_STATS = "moe_stats"


def _greedy(decode_step):
    """The greedy decode loop as one program: ``generate(params, cache,
    logits, n, size)`` takes the first token from ``logits``, then runs
    ``n`` (traced) decode steps, each writing its input token into a
    ``size``-token (static) output buffer and taking the next token from
    the step's logits. Returns (tokens [size], cache): the cache holds every
    generated token (and, for a MoE model, the expert work of all ``n``
    steps); the last step's logits are discarded."""
    def generate(params, cache, logits, n, size):
        def step(i, carry):
            tok, cache, out, work = carry
            out = out.at[i].set(tok[0])
            logits, cache = decode_step(params, cache, tok)
            if work is not None:
                work = work + cache[MOE_STATS]
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32), cache, out,
                    work)

        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        work = (jnp.zeros_like(cache[MOE_STATS]) if MOE_STATS in cache
                else None)
        _, cache, out, work = jax.lax.fori_loop(
            0, n, step, (tok, cache, jnp.zeros((size,), jnp.int32), work))
        if work is not None:
            cache = {**cache, MOE_STATS: work}
        return out, cache

    return generate


def _moe_counters(work) -> dict:
    """A span's MoE counters from a call's int32 [rows, experts]."""
    return {"moe_rows": int(work[0]), "moe_experts": int(work[1])}


def weights_devices(engine) -> frozenset:
    """The devices that hold ``engine``'s weights, where its programs run
    (none for an engine without weights, such as an analytic one)."""
    return frozenset(d for a in jax.tree.leaves(getattr(engine, "params", None))
                     for d in a.devices())


def _shared_fns(cfg: ModelConfig, max_len: int):
    key = (cfg, max_len)
    if key not in _SHARED:
        model = build_model(cfg)
        _SHARED[key] = {
            "model": model,
            "init": jax.jit(model.init),
            "prefill": jax.jit(
                lambda p, b: model.prefill(p, {**b, "max_len": max_len})),
            "decode": jax.jit(model.decode_step),
            # not donated: the cache may be a stored session's own
            "generate": jax.jit(_greedy(model.decode_step),
                                static_argnames="size"),
            "extend": jax.jit(model.extend),
        }
    return _SHARED[key]


class AgentEngine:
    """One agent's inference engine: real JAX prefill/extend/decode with
    per-dialogue KV/state reuse (see module docstring)."""

    def __init__(self, cfg: ModelConfig, *, seed: int = 0, speed: float = 1.0,
                 cache_slots: int = 6, max_len: int = 1024,
                 max_new_tokens: int = 8, device=None):
        self.cfg = cfg
        shared = _shared_fns(cfg, max_len)
        self.model = shared["model"]
        key = jax.random.PRNGKey(seed)
        # one fused program run where the key is committed: the weights are
        # born on the agent's device in their own dtype (no float32 staging
        # copy), and the jitted prefill/extend/decode then run there,
        # returning caches that live there too
        self.params = shared["init"](
            key if device is None else jax.device_put(key, device))
        self.device = device
        self.speed = speed
        self.cache_slots = cache_slots
        self.max_len = max_len
        self.max_new = max_new_tokens
        self.sessions: dict[str, SessionCache] = {}
        self.recurrent = self.model.family in ("rwkv", "zamba")
        self._prefill_j = shared["prefill"]
        self._decode_j = shared["decode"]
        self._extend_j = shared["extend"]
        self._generate_j = shared["generate"]
        self.evictions = 0
        # the serving stack's RoutingProfiler, attached by the cluster:
        # engine.serve / prefill|extend / decode spans (None: no-ops)
        self.profiler = None
        # the context in which `serve` waits on the device: a cluster that
        # serves several engines at once installs one that lets other
        # threads run host code meanwhile
        self.wait = nullcontext

    def warmup(self, prefill_buckets=(32, 64, 128, 256, 512),
               extend_buckets=(16, 32, 64)) -> None:
        """Pre-compile the shape buckets so TTFT excludes XLA compile time:
        prefills, extends, the decode loop and the probe step of a prompt
        that is cached whole."""
        for b in prefill_buckets:
            if b > self.max_len:
                continue
            self.serve("__warm__", np.arange(1, b + 1, dtype=np.int32) %
                       (self.cfg.vocab_size - 1) + 1, max_new_tokens=1)
        prev = self.sessions.get("__warm__")
        if prev is not None:
            self.serve("__warm__", prev.prompt, max_new_tokens=1)
        for b in extend_buckets:
            ext = np.arange(1, b, dtype=np.int32) % (self.cfg.vocab_size - 1) + 1
            prev = self.sessions.get("__warm__")
            if prev is None:
                continue
            self.serve("__warm__", np.concatenate([prev.prompt, ext]),
                       max_new_tokens=1)
        self.drop_session("__warm__")

    # ---------------- cache management ----------------
    def _evict_lru(self, now: float):
        while len(self.sessions) > self.cache_slots:
            victim = min(self.sessions, key=lambda k: self.sessions[k].last_used)
            del self.sessions[victim]
            self.evictions += 1

    def _truncate_attn_cache(self, cache, keep: int):
        """Invalidate cached positions >= keep (attention archs only)."""
        new = dict(cache)
        sp = cache["slot_pos"]
        new["slot_pos"] = jnp.where(sp < keep, sp, -1)
        new["pos"] = jnp.full_like(cache["pos"], keep)
        return new

    def _session_hit(self, prompt: np.ndarray, sess: SessionCache) -> int:
        """Cached prompt tokens this session would grant (arch rules):
        attention reuses any common prefix; recurrent state only an exact
        extension of the session's full prompt."""
        l = lcp_length(prompt, sess.prompt)
        if self.recurrent:
            return l if (l == len(sess.prompt) and l <= len(prompt)) else 0
        return l

    def _pick_session(self, dialogue_id: str, prompt: np.ndarray, parents):
        """Best cache candidate among the session's own entry and its DAG
        parent-step sessions (handoff fork: a child step's prompt starts
        with its parents' contexts, so a parent's cache is a warm prefix).
        Forking is safe — cache pytrees are immutable and extend/truncate
        return fresh dicts, so the parent's entry is never mutated."""
        sess = self.sessions.get(dialogue_id)
        if not parents:
            return sess
        best = self._session_hit(prompt, sess) if sess is not None else 0
        for pid in parents:
            ps = self.sessions.get(pid)
            if ps is not None and self._session_hit(prompt, ps) > best:
                best, sess = self._session_hit(prompt, ps), ps
        return sess

    # ---------------- serving ----------------
    def serve(self, dialogue_id: str, prompt: np.ndarray, now: float = 0.0,
              max_new_tokens: int | None = None,
              parents: tuple = ()) -> ServeResult:
        """Serve one request: cache-aware prefill/extend + greedy decode,
        measuring TTFT/total wall-clock (scaled by agent speed) and exact
        cached-token counts.  ``parents`` names sibling session keys whose
        cached state may be forked (DAG handoffs); the result is stored
        under ``dialogue_id`` regardless.

        The whole call is the profiler's ``engine.serve`` span (``session``,
        the routing call's ``batch``, ``chip``, the id of the device that
        holds the weights; counters ``mode``, ``n_prompt``,
        ``n_hit``, ``n_gen``, ``evicted``); its self time is the host
        preparation around the ``engine.prefill``/``engine.extend`` and
        ``engine.decode`` (counters ``steps``, the decode steps run, and
        ``syncs``, its device-to-host reads) spans. A MoE model's
        prefill/extend and decode spans also count ``moe_rows``, the
        (token, expert) rows its routed experts computed, and
        ``moe_experts``, the experts that got at least one row, each summed
        over layers and steps."""
        prof = self.profiler
        evictions = self.evictions
        with phase_scope(prof, "engine.serve", session=dialogue_id,
                         batch=prof.batch if prof is not None else -1,
                         chip=(min(d.id for d in weights_devices(self))
                               if prof is not None else -1)) as span:
            res, mode = self._serve(dialogue_id,
                                    np.asarray(prompt, dtype=np.int32), now,
                                    max_new_tokens or self.max_new, parents)
            span.set(mode=mode, n_prompt=res.n_prompt, n_hit=res.n_hit,
                     n_gen=res.n_gen, evicted=self.evictions - evictions)
        return res

    def _serve(self, dialogue_id: str, prompt: np.ndarray, now: float,
               max_new: int, parents: tuple) -> tuple:
        """`serve`'s body; returns (ServeResult, cache mode)."""
        prof = self.profiler
        n_prompt = len(prompt)
        sess = self._pick_session(dialogue_id, prompt, parents)

        n_hit = 0
        mode = "fresh"
        if sess is not None:
            l = lcp_length(prompt, sess.prompt)
            if self.recurrent:
                if l == len(sess.prompt) and l <= n_prompt:
                    n_hit, mode = l, "extend"
            else:
                if l == n_prompt and l == len(sess.prompt):
                    n_hit, mode = l, "identical"
                elif l > 0:
                    n_hit, mode = l, "extend"

        t0 = time.perf_counter()
        with phase_scope(prof, "engine.prefill" if mode == "fresh"
                         else "engine.extend") as first:
            logits, cache, work = self._first_logits(prompt, sess, mode,
                                                     n_hit)
            with self.wait():
                jax.block_until_ready(logits)
                t_first = time.perf_counter()
            # under a cluster's `wait` the host may still be serving another
            # engine when the logits are ready; that turn is not this
            # request's time
            lent = (time.perf_counter() - t_first
                    if self.wait is not nullcontext else 0.0)
            if work is not None:
                # the program has finished: this reads its ready counters
                # (a span takes its counters only while it is open)
                first.set(**_moe_counters(np.asarray(work)))

        # greedy decode: one program, its tokens (and a MoE model's expert
        # counters) read back once (the output buffer's size is bucketed so
        # few sizes compile)
        with phase_scope(prof, "engine.decode") as decode:
            out, cache = self._generate_j(self.params, cache, logits,
                                          np.int32(max_new),
                                          size=_bucket(max_new, lo=8))
            with self.wait():
                out, cache = jax.block_until_ready((out, cache))
                out, work = jax.device_get((out, cache.get(MOE_STATS)))
                gen = out[:max_new]
                t_end = time.perf_counter()
            decode.set(steps=max_new, syncs=1)
            if work is not None:
                decode.set(**_moe_counters(work))

        # store the state covering prompt + generated answer (next turn will
        # extend past it, mirroring vLLM prefix caching)
        full = np.concatenate([prompt, gen])
        self.sessions[dialogue_id] = SessionCache(cache, full, last_used=now)
        self._evict_lru(now)

        ttft = (t_first - t0) / self.speed
        total = (t_end - t0 - lent) / self.speed
        return ServeResult(gen, ttft, total, n_prompt, min(n_hit, n_prompt),
                           len(gen)), mode

    def _first_logits(self, prompt: np.ndarray, sess, mode: str,
                      n_hit: int):
        """The path to the first token's logits: a fresh prefill, an
        extend past the ``n_hit`` cached tokens, or (everything cached) a
        probe step on the truncated cache.  Returns (logits, cache, work):
        ``work`` is a MoE model's expert counters of the call that made the
        logits (None for other models)."""
        if mode == "identical":
            # nothing to prefill; just decode from current state
            cache = sess.cache
            logits, probe = self._decode_noop(cache)
            return logits, cache, probe.get(MOE_STATS)
        if mode == "extend":
            cache = sess.cache
            if not self.recurrent:
                cache = self._truncate_attn_cache(cache, n_hit)
            if n_hit == len(prompt):
                logits, probe = self._decode_noop(cache)
                return logits, cache, probe.get(MOE_STATS)
            suffix = prompt[n_hit:]
            if self.recurrent:
                # recurrent state cannot mask padding: exact-length extend
                # (jit specializes per suffix length; lengths are few)
                pad = suffix
            else:
                pad = np.zeros(_bucket(len(suffix)), np.int32)
                pad[: len(suffix)] = suffix
            logits, cache = self._extend_j(self.params, cache,
                                           jnp.asarray(pad[None]),
                                           jnp.asarray([len(suffix)],
                                                       jnp.int32))
            return logits, cache, cache.get(MOE_STATS)
        n_prompt = len(prompt)
        if self.recurrent:
            pad = prompt
        else:
            pad = np.zeros(_bucket(n_prompt), np.int32)
            pad[:n_prompt] = prompt
        batch = {"tokens": jnp.asarray(pad[None]),
                 "lens": jnp.asarray([n_prompt], jnp.int32)}
        if self.cfg.is_encdec:
            batch["frames"] = jnp.zeros((1, self.cfg.src_len,
                                         self.cfg.d_model), jnp.float32)
        logits, cache = self._prefill_j(self.params, batch)
        return logits, cache, cache.get(MOE_STATS)

    def _decode_noop(self, cache):
        """Cheap logits for the 'everything cached' path: one decode step on
        the BOS-free cache without committing its state."""
        tok = jnp.zeros((cache["pos"].shape[0],), jnp.int32)
        return self._decode_j(self.params, cache, tok)

    def drop_session(self, dialogue_id: str) -> None:
        """Forget one dialogue's cached state."""
        self.sessions.pop(dialogue_id, None)
