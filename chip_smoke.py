#!/usr/bin/env python3
"""Run the IEMAS served path end to end on the chip.

    python chip_smoke.py                # one TPU chip
    python chip_smoke.py --four-chips   # four chips, one agent per chip

One chip: two qwen3-8b agents at the published widths (d_model 4096, 32
query / 8 KV heads of 128, d_ff 12288, vocab 151936, qk-norm, bf16; depth
cut to 4 of 36 layers; random weights from ``--seed``) serve a closed-loop
``coqa_like`` workload behind the IEMAS router, through the entry points a
user calls (`SimCluster`, `make_router`, `run_workload`). The router runs
the fused device-resident routing step with the compiled Pallas bid round
(``solver="pallas"``, ``fused=True``, one hub, warm-started prices).
The run checks:

* every request is served and none failed; the KV hit rate is positive;
  the market's surplus is non-negative (weak budget balance);
* every routing program holds the compiled bid kernel (``tpu_custom_call``);
* routing reference: on every routed batch, the assignment's welfare is
  within the auction's certified ``2·n·ε`` of the exact MCMF optimum of the
  same welfare matrix, solved on the host;
* engine reference: a second-turn request served through the cached extend
  path gives the same greedy first token as a fresh prefill of the whole
  prompt, with logits close at a bf16 tolerance (on a probe whose top token
  leads by more than twice that tolerance, see `check_extend`);
* the compiled LCP affinity kernel equals the repo's reference.

Four chips (``--four-chips``): four such agents, one per chip, behind the
same router. Each agent's parameters and caches must live on its own
device, and each agent's greedy tokens for a fixed probe prompt must equal
those of the same parameters run on device 0. No other phase runs.

The script reads ``jax.devices()`` first and exits non-zero, printing no
result, unless the platform is ``tpu``; it never falls back to the CPU or
to interpret mode. Any failed check ends the run with a non-zero exit. The
last line of standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from collections import Counter
from pathlib import Path

DEPTH = 4               # layers kept of qwen3-8b's 36
MAX_NEW = 6             # generated tokens per request
N_DIALOGUES = 16        # more than one agent's 12 slots: every agent serves
BF16_REL_TOL = 2.0 ** -5  # logit tolerance, relative to the largest logit
WELFARE_ATOL = 1e-4     # float slack on top of the certified gap
PROBE_TRIES = 32        # probes drawn for a decisive extend-vs-fresh check


def log(phase: str, **fields) -> None:
    """One progress line per phase (never the last line of a run)."""
    print(json.dumps({"phase": phase, **fields}, default=float), flush=True)


def require_tpu(n_chips: int):
    """The visible devices, if they are at least ``n_chips`` TPUs."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}", flush=True)
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{d0.platform!r}); this script runs only on the chip")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices


def agent_config(depth: int = DEPTH):
    """qwen3-8b at its published widths with the depth cut to ``depth``."""
    from repro.configs import get_config

    return dataclasses.replace(get_config("qwen3-8b"), n_layers=depth)


def describe(cfg) -> dict:
    """Parameter counts and bf16 bytes, whole and per agent as cut."""
    from repro.configs import param_counts

    full = dataclasses.replace(cfg, n_layers=36)
    width = 2  # bf16
    return {"layers": cfg.n_layers, "of_layers": full.n_layers,
            "params_per_agent": param_counts(cfg)["total"],
            "bytes_per_agent": param_counts(cfg)["total"] * width,
            "params_full_model": param_counts(full)["total"],
            "bytes_full_model": param_counts(full)["total"] * width}


def build_cluster(cfg, n_agents: int, seed: int, *, warmup: bool):
    """`SimCluster` of real engines running ``cfg``; returns it with the
    set-up (parameter init) and compile (engine warmup) seconds."""
    import jax

    from repro.serving import SimCluster

    t0 = time.perf_counter()
    cluster = SimCluster(n_agents=n_agents, seed=seed, max_new_tokens=MAX_NEW,
                         engine_config=cfg)
    jax.block_until_ready([rt.engine.params for rt in cluster.agents.values()])
    setup_s = time.perf_counter() - t0
    compile_s = 0.0
    if warmup:
        t0 = time.perf_counter()
        for rt in cluster.agents.values():
            rt.engine.warmup()
        compile_s = time.perf_counter() - t0
    return cluster, setup_s, compile_s


class FusedProbe:
    """Records what a router's fused routing step ran: each program variant
    with the argument shapes of its first call, and each batch's market
    (the packaged auction result and the capacities it was solved under)."""

    def __init__(self, step):
        import jax

        self.programs: dict = {}
        self.markets: list = []
        make, run = step._program, step.step

        def program(warm, has_parents, budget):
            prog = make(warm, has_parents, budget)

            def call(*args, **kw):
                if (warm, has_parents, budget) not in self.programs:
                    specs = jax.tree.map(
                        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
                    self.programs[(warm, has_parents, budget)] = (
                        prog, specs, kw)
                return prog(*args, **kw)

            return call

        def step_(requests, live, telemetry, caps, start_prices=None):
            out = run(requests, live, telemetry, caps,
                      start_prices=start_prices)
            self.markets.append((out[5], list(caps)))
            return out

        step._program = program
        step.step = step_

    def kernel_lowered(self) -> dict:
        """Per program variant: whether its lowered text holds the compiled
        Pallas kernel (``tpu_custom_call``)."""
        return {str(key): "tpu_custom_call" in
                prog.lower(*specs, **kw).as_text()
                for key, (prog, specs, kw) in self.programs.items()}


def fused_pallas_router(cluster):
    """IEMAS router on the fused step with the Pallas bid round, probed."""
    from repro.configs.iemas_cluster import RouterConfig
    from repro.serving import make_router

    router = make_router(cluster, RouterConfig(
        solver="pallas", fused=True, n_hubs=1, warm_start=True))
    return router, FusedProbe(router._fused)


def serve_workload(cluster, router, cfg, n_dialogues: int, seed: int) -> dict:
    """Closed-loop coqa_like run over the full vocabulary; checks that every
    request was served and the market's accounts."""
    from repro.serving import WorkloadSpec, generate, run_workload

    dialogues = generate(WorkloadSpec("coqa_like", n_dialogues=n_dialogues,
                                      seed=seed + 1, vocab=cfg.vocab_size))
    expected = sum(len(d.turns) for d in dialogues)
    t0 = time.perf_counter()
    out = run_workload(cluster, router, dialogues, max_new_tokens=MAX_NEW)
    out["wall_s"] = time.perf_counter() - t0
    out["expected_requests"] = expected
    out["surplus"] = router.accounts["surplus"]
    if out["unfinished_dialogues"] or out.get("n") != expected:
        raise AssertionError(f"served {out.get('n')} of {expected} requests "
                             f"({out['unfinished_dialogues']} dialogues "
                             f"unfinished)")
    if out["dispatched_requests"] != expected:
        raise AssertionError(f"{out['dispatched_requests']} dispatches for "
                             f"{expected} requests: some failed and retried")
    if not out["kv_hit_rate"] > 0.0:
        raise AssertionError(f"kv_hit_rate {out['kv_hit_rate']} is not > 0")
    if not out["surplus"] >= 0.0:
        raise AssertionError(f"surplus {out['surplus']} < 0: weak budget "
                             f"balance violated")
    out["max_generated_id"] = max(int(r.output_tokens.max())
                                  for r in cluster.records)
    out["served_by"] = dict(Counter(r.agent_id for r in cluster.records))
    return out


def check_routing(markets) -> dict:
    """Each routed batch's welfare vs the exact MCMF optimum on the host."""
    from repro.core.solvers.mcmf import solve_allocation

    worst = 0.0
    for res, caps in markets:
        w = res.weights
        got = float(sum(w[j, i] for j, i in enumerate(res.assignment)
                        if i >= 0))
        _, opt, _ = solve_allocation(w, caps)
        bound = float(res.solver_stats["gap_bound"])
        if not (opt - got <= bound + WELFARE_ATOL
                and got <= opt + WELFARE_ATOL):
            raise AssertionError(f"fused/pallas welfare {got} vs MCMF {opt}: "
                                 f"gap beyond the certified {bound}")
        worst = max(worst, opt - got)
    if not markets:
        raise AssertionError("the router routed no batch")
    return {"batches": len(markets), "worst_gap": worst}


def check_extend(engine, vocab: int, seed: int) -> dict:
    """Second turn through the cached extend path vs a fresh prefill of the
    whole prompt, on one full-width agent.

    With random weights the logits over the whole vocabulary are nearly
    flat, so the top two often lie within bf16 noise of each other, where
    no order of bf16 arithmetic promises the same argmax. Probes are drawn
    from ``seed`` until the fresh prefill's top token leads the runner-up by
    more than twice the tolerance: there logits that agree within the
    tolerance must give the same greedy token, and both are required."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.affinity import lcp_length
    from repro.serving.engine import _bucket

    def padded(toks):
        pad = np.zeros(_bucket(len(toks)), np.int32)
        pad[: len(toks)] = toks
        return jnp.asarray(pad[None]), jnp.asarray([len(toks)], jnp.int32)

    rng = np.random.default_rng(seed)
    for tries in range(1, PROBE_TRIES + 1):
        p1 = rng.integers(1, vocab, 40, dtype=np.int32)
        r1 = engine.serve("__probe__", p1)
        p2 = np.concatenate([p1, r1.output_tokens,
                             rng.integers(1, vocab, 10, dtype=np.int32)])
        toks, lens = padded(p2)
        fresh, _ = engine._prefill_j(engine.params, {"tokens": toks,
                                                     "lens": lens})
        fresh = np.asarray(fresh[0], np.float32)
        tol = BF16_REL_TOL * max(1.0, float(np.abs(fresh).max()))
        second, top = np.partition(fresh, -2)[-2:]
        if top - second > 2 * tol:
            break
        engine.drop_session("__probe__")
    else:
        raise AssertionError(f"no probe in {PROBE_TRIES} whose greedy token "
                             f"leads by more than twice the tolerance")
    sess = engine.sessions["__probe__"]
    n_hit = lcp_length(p2, sess.prompt)
    toks, lens = padded(p2[n_hit:])
    ext, _ = engine._extend_j(engine.params,
                              engine._truncate_attn_cache(sess.cache, n_hit),
                              toks, lens)
    r2 = engine.serve("__probe__", p2)
    engine.drop_session("__probe__")
    ext = np.asarray(ext[0], np.float32)
    diff = float(np.abs(ext - fresh).max())
    t_ext, t_fresh = int(ext.argmax()), int(fresh.argmax())
    if r2.n_hit != n_hit or n_hit != len(p1) + len(r1.output_tokens):
        raise AssertionError(f"second turn hit {r2.n_hit} cached tokens, "
                             f"expected {len(p1) + len(r1.output_tokens)}")
    if int(r2.output_tokens[0]) != t_ext:
        raise AssertionError("served extend token differs from the extend "
                             "logits' argmax")
    if diff > tol or t_ext != t_fresh:
        raise AssertionError(f"extend vs fresh prefill: max |logit diff| "
                             f"{diff} (tol {tol}), tokens {t_ext} vs "
                             f"{t_fresh}")
    return {"probes_drawn": tries, "n_hit": n_hit, "token": t_ext,
            "top_margin": float(top - second), "max_abs_logit_diff": diff,
            "tol": tol}


def check_lcp(seed: int) -> dict:
    """The LCP affinity kernel, run as the backend resolves it, against the
    repo's reference on prefix-structured ledgers spanning several token
    tiles."""
    import numpy as np

    from repro.kernels.lcp_affinity import lcp_affinity
    from repro.kernels.ref import lcp_ref

    rng = np.random.default_rng(seed)
    n, m, l = 16, 128, 768
    p = rng.integers(1, 1000, (n, l), dtype=np.int32)
    led = np.repeat(p[:, None, :], m, axis=1)
    cut = rng.integers(0, l + 1, (n, m))
    for j in range(n):
        for i in range(m):
            if cut[j, i] < l:
                led[j, i, cut[j, i]] = 0
    got = np.asarray(lcp_affinity(p, led))
    if not np.array_equal(got, lcp_ref(p, led)):
        raise AssertionError("lcp_affinity differs from lcp_ref")
    return {"shape": [n, m, l],
            "kernel_lowered": "tpu_custom_call"
            in lcp_affinity.lower(p, led).as_text()}


def greedy_tokens(engine, params, prompt, n: int) -> list:
    """``n`` greedy tokens of ``engine``'s model under ``params`` (runs on
    the device that holds them)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.engine import _bucket

    pad = np.zeros(_bucket(len(prompt)), np.int32)
    pad[: len(prompt)] = prompt
    logits, cache = engine._prefill_j(params, {
        "tokens": jnp.asarray(pad[None]),
        "lens": jnp.asarray([len(prompt)], jnp.int32)})
    out = []
    for _ in range(n):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(int(tok[0]))
        logits, cache = engine._decode_j(params, cache, tok)
    return out


def on_devices(tree) -> set:
    """The devices holding any leaf of ``tree``."""
    import jax

    return set().union(*(leaf.devices() for leaf in jax.tree.leaves(tree)))


def one_chip(devices, seed: int) -> None:
    """The one-chip phases (see the module docstring)."""
    cfg = agent_config()
    log("config", arch=cfg.name, d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.hd, d_ff=cfg.d_ff,
        vocab=cfg.vocab_size, qk_norm=cfg.qk_norm, dtype=cfg.dtype,
        agents=2, **describe(cfg))
    cluster, setup_s, compile_s = build_cluster(cfg, 2, seed, warmup=True)
    log("setup", setup_s=setup_s, compile_s=compile_s)
    router, probe = fused_pallas_router(cluster)
    out = serve_workload(cluster, router, cfg, N_DIALOGUES, seed)
    log("serve", **{k: out[k] for k in (
        "n", "expected_requests", "dispatched_requests", "kv_hit_rate",
        "surplus", "rounds", "wall_s", "max_generated_id", "served_by")})
    lowered = probe.kernel_lowered()
    log("bid_kernel", programs=lowered)
    if not lowered or not all(lowered.values()):
        raise AssertionError(f"fused routing without the compiled Pallas "
                             f"bid round: {lowered}")
    log("routing_reference", **check_routing(probe.markets))
    engine = next(iter(cluster.agents.values())).engine
    log("engine_reference", **check_extend(engine, cfg.vocab_size, seed))
    lcp = check_lcp(seed)
    log("lcp_kernel", **lcp)
    if not lcp["kernel_lowered"]:
        raise AssertionError("lcp_affinity ran without the compiled kernel")
    stats = devices[0].memory_stats() or {}
    log("memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        bytes_limit=stats.get("bytes_limit"))


def four_chips(devices, seed: int) -> None:
    """Four agents, one per chip, behind one router (module docstring)."""
    import jax
    import numpy as np

    cfg = agent_config()
    log("config", arch=cfg.name, agents=4, **describe(cfg))
    cluster, setup_s, _ = build_cluster(cfg, 4, seed, warmup=False)
    log("setup", setup_s=setup_s)
    engines = [rt.engine for rt in cluster.agents.values()]
    router, _ = fused_pallas_router(cluster)
    out = serve_workload(cluster, router, cfg, N_DIALOGUES, seed)
    log("serve", **{k: out[k] for k in (
        "n", "expected_requests", "kv_hit_rate", "surplus", "wall_s",
        "served_by")})
    placed = []
    for i, eng in enumerate(engines):
        held = on_devices(eng.params).union(
            *(on_devices(s.cache) for s in eng.sessions.values()))
        if held != {devices[i]}:
            raise AssertionError(f"agent {i}'s arrays are on {held}, not on "
                                 f"device {devices[i]}")
        placed.append(devices[i].id)
    log("placement", agent_device_ids=placed,
        sessions=[len(e.sessions) for e in engines])
    prompt = np.random.default_rng(seed).integers(1, cfg.vocab_size, 48,
                                                  dtype=np.int32)
    probes = []
    for i, eng in enumerate(engines):
        mine = greedy_tokens(eng, eng.params, prompt, 8)
        on0 = jax.device_put(eng.params, devices[0])
        ref = greedy_tokens(eng, on0, prompt, 8)
        del on0
        if mine != ref:
            raise AssertionError(f"agent {i}: tokens on device {i} {mine} "
                                 f"!= on device 0 {ref}")
        probes.append(mine)
    log("probe_vs_device0", tokens=probes)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="four agents, one per chip (needs four chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_tpu(4 if args.four_chips else 1)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.utils.compile_cache import enable_compile_cache

    log("compile_cache", dir=enable_compile_cache())
    if args.four_chips:
        four_chips(devices[:4], args.seed)
    else:
        one_chip(devices, args.seed)
    d0 = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
