"""One run of one cell: set-up, warm pass, measured window, metrics, check.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run builds the cell's system from its configuration and traffic files
(`system.System`), makes the weights from the seed, compiles the engine
programs for the traffic's shape buckets, and serves ``warm_requests``
requests of the same stream so that routing programs, predictors and caches
are warm. That is the set-up (``setup_s``, from process start). The window
then drives the same stream for ``--seconds`` of wall clock. With
``--trace 1`` the window runs under the JAX profiler and the result holds
the cell's per-layer metrics; otherwise its end-to-end metrics.

After the window the run reads the device's peak memory, frees the
program's state and checks what the window produced against the plain
references (`reference/`): every routed market's allocation and Clarke
payments (a seeded sample of them where a window routes many), and in
served cells the logits of a seeded sample of served requests. Each number
compared is printed beside its limit as the last lines of standard error
and under ``checks``, the last key of the result line.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from types import SimpleNamespace

import numpy as np

import counts
import loader

TRACE_DIR = loader.ROOT / ".bench_trace"
#: a traced run traces the first this many seconds of its window: a trace
#: of every operation grows by hundreds of MB a minute in the busiest cell
TRACE_SECONDS = 10.0
CACHE_DIR = loader.ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_compile_cache() -> None:
    """JAX's persistent compilation cache, at a fixed path in the checkout;
    every program is cached, so only a checkout's first run compiles."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips(n: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return devices[:n]


def _p95(xs) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), 95))


def _sample(rng, items: list, k: int, key) -> list:
    """``k`` items drawn by ``rng``, always holding the largest by ``key``."""
    if len(items) <= k:
        return list(items)
    top = max(range(len(items)), key=lambda i: key(items[i]))
    rest = [i for i in range(len(items)) if i != top]
    pick = rng.choice(len(rest), size=k - 1, replace=False)
    return [items[top]] + [items[rest[i]] for i in sorted(pick)]


def _agent_share(served: list, agent: str) -> dict:
    """What one agent served in the window: requests, and the share of
    their prompt tokens it found cached (%)."""
    mine = [r for r in served if r["agent"] == agent]
    prompt = sum(r["n_prompt"] for r in mine)
    return {"served": len(mine),
            "kv_hit_share": (100.0 * sum(r["n_hit"] for r in mine) / prompt
                             if prompt else None)}


def check_routing(rec, check: dict, rng, controls=()) -> dict:
    """Worst numbers over the window's routed markets (or a seeded sample):
    Phase 2's allocation and payments against the exact solver, and Phase
    1's affinity against a replay of the ledger from the run's history."""
    ref = loader.reference("assignment")
    aff = loader.reference("affinity")
    calls = [c for c in rec.route_calls if c["values"] is not None]
    k = int(check.get("markets", len(calls)))
    pick = _sample(rng, calls, k, key=lambda c: c["n"] * c["m"])
    replayed = aff.replay(rec.events, {c["event"] for c in pick})
    worst = {"route.over_capacity": 0.0, "route.welfare_gap": 0.0,
             "route.payment_err": 0.0, "route.affinity_err": 0.0}
    ctl = {c: dict.fromkeys(worst, 0.0) for c in controls}

    def fold(into, got):
        for name, v in got.items():
            into["route." + name] = max(into["route." + name], float(v))

    for c in pick:
        col = {aid: i for i, aid in enumerate(c["live"])}
        alloc = [col[a] if a is not None else -1 for a in c["agents"]]
        o_ref = replayed[c["event"]]
        fold(worst, ref.check_market(c["values"], c["costs"], c["caps"],
                                     alloc, c["payments"]))
        fold(worst, aff.check(c["affinity"], o_ref))
        for q in controls:
            fold(ctl[q], ref.check_market(c["values"], c["costs"],
                                          c["caps"], alloc, c["payments"],
                                          quant=q))
            fold(ctl[q], aff.check(c["affinity"], o_ref, quant=q))
    return {"markets": len(pick), "numbers": worst, "controls": ctl}


def check_engine(rec, config: dict, seed: int, check: dict, rng,
                 agent_index: dict, controls=()) -> dict:
    """Widest gap of a served token's logit below the reference's best, over
    a seeded sample of the window's served requests (the longest in it)."""
    family = loader.reference(config["engine"]["family"])
    pick = _sample(rng, rec.served, int(check["requests"]),
                   key=lambda r: r["n_prompt"] + r["n_gen"])
    samples: dict = {}
    for r in pick:
        samples.setdefault(agent_index[r["agent"]], []).append(
            (r["prompt"], r["output"]))
    gaps = family.served_gaps(config, seed, samples, controls=controls)
    return {"requests": len(pick), "tokens": gaps["tokens"],
            "numbers": {"engine.logit_gap": gaps["program"]},
            "controls": {c: {"engine.logit_gap": gaps[c]} for c in controls}}


def judge(numbers: dict, limits: dict) -> tuple:
    """Each compared number beside its limit, and whether every one holds.
    The program's ``correct`` and each control's are decided here alike."""
    checks = {name: {"value": float(v), "limit": float(limits[name])}
              for name, v in numbers.items() if name in limits}
    return checks, bool(checks) and all(c["value"] <= c["limit"]
                                        for c in checks.values())


def per_layer(spec: dict, cell: dict, ctx) -> dict:
    out = {}
    for m in spec["per_layer"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        v = loader.metric(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run_cell(spec: dict, cell: dict, config: dict, traffic: dict, seed: int,
             seconds: float, trace: bool, t_start: float, *,
             require_chip: bool = True, hook=None, controls: dict | None = None
             ) -> dict:
    """One run of ``cell`` (see the module docstring). ``hook(system)`` runs
    once the program is built, before the benchmark wraps it (the checks
    plant faults in the program there); ``controls`` maps
    "routing"/"engine" to reduced-precision controls whose readings are
    returned beside the program's (the control runs, not benchmark runs)."""
    import jax

    use_compile_cache()
    # the cell's own chips; off the chip (the checks) the CPU's devices
    devices = (chips(cell["chips"]) if require_chip
               else jax.devices()[: cell["chips"]])
    sys.path.insert(0, str(loader.ROOT / "src"))
    import system as system_mod

    sysm_phases = {"start": time.perf_counter() - t_start}
    t = time.perf_counter()
    sysm = system_mod.System(config, traffic, seed, devices, hook=hook)
    sysm_phases["build"] = time.perf_counter() - t
    t = time.perf_counter()
    sysm.warm_engines()
    sysm_phases["warm_engines"] = time.perf_counter() - t
    t = time.perf_counter()
    sysm.warm(int(traffic["warm_requests"]))
    sysm_phases["warm_pass"] = time.perf_counter() - t
    fused0 = sysm.profiler.fused_retraces
    engine0 = sysm.engine_programs()
    rec = sysm.rec
    tr = None
    traced = {}
    mark = None
    if trace:
        import shutil

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        rec.annotate = True
        ann = jax.profiler.TraceAnnotation("bench.window")
        ann.__enter__()
        traced["t0"] = time.perf_counter()

        def mark():
            traced["t1"] = time.perf_counter()
            ann.__exit__(None, None, None)
            rec.annotate = False
            jax.profiler.stop_trace()

    t_window = time.perf_counter()
    setup_s = t_window - t_start
    w0, w1 = sysm.window(seconds, mark=mark, mark_after=TRACE_SECONDS)
    window_s = w1 - w0
    fused_compiles = sysm.profiler.fused_retraces - fused0
    engine_compiles = sysm.engine_programs() - engine0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    agent_index = {aid: i for i, aid in enumerate(sysm.cluster.agents)}
    placement = sysm.placement()
    by_agent = {aid: _agent_share(rec.served, aid) for aid in agent_index}
    model = config if sysm.model_cfg is not None else None
    sysm.free()
    del sysm
    gc.collect()

    served = rec.served
    metrics: dict = {}
    if trace:
        reduce = loader.module(loader.BENCH / "trace" / "reduce.py")
        tr = reduce.Trace.load(reduce.find_trace(str(TRACE_DIR)),
                               chips=len(devices))
        # the per-layer metrics read the traced part of the window only
        t_end = traced["t1"]
        ctx = SimpleNamespace(
            window_s=t_end - w0,
            spans=[s for s in rec.spans if s[2] <= t_end],
            served=[r for r in served if r["serve_t1"] <= t_end],
            route_calls=[c for c in rec.route_calls if c["t1"] <= t_end],
            trace=tr, model=model,
            counts=counts, fused_compiles=fused_compiles,
            # off the chip (the checks only) the table's v5e row stands in
            peaks=loader.peaks(devices[0].device_kind if require_chip
                               else "TPU v5 lite"))
        metrics = per_layer(spec, cell, ctx)
    else:
        route_ms = [1e3 * (c["t1"] - c["t0"]) for c in rec.route_calls]
        e2e = {
            "req_per_s": len(served) / window_s,
            "route_ms_p95": _p95(route_ms) if route_ms else None,
            "cost_per_req": (sum(r["cost"] for r in served) / len(served)
                             if served else None),
        }
        if model is not None and served:
            e2e["ttft_ms_p95"] = _p95([1e3 * (r["first_token_t"]
                                              - r["route_t0"])
                                       for r in served])
        e2e["setup_s"] = setup_s
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        for m in spec["end_to_end"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": units[m["name"]]}

    # ---- correctness, once the program's state is freed
    controls = controls or {}
    rng = np.random.default_rng([seed, 0xC4EC])
    check = config["check"]
    found = check_routing(rec, check, rng, controls.get("routing", ()))
    numbers = dict(found["numbers"])
    control_numbers = found["controls"]
    sampled = {"markets": found["markets"]}
    if model is not None:
        eng = check_engine(rec, config, seed, check, rng, agent_index,
                           controls.get("engine", ()))
        numbers.update(eng["numbers"])
        for c, v in eng["controls"].items():
            control_numbers.setdefault(c, {}).update(v)
        sampled.update(requests=eng["requests"], tokens=eng["tokens"])
    checks, holds = judge(numbers, check["limits"])
    correct = bool(served) and holds
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    if tr is not None:
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
    result = {"correct": correct, "attempted": len(served) + rec.failed,
              "failed": rec.failed, "metrics": metrics, "device": device}
    if tr is not None:
        result["breakdown"] = {"device_ops": tr.top_ops(),
                               "idle_gaps": tr.idle_gaps()}
    result["window"] = {
        "seconds": window_s, "served": len(served),
        "route_calls": len(rec.route_calls),
        "fused_compiles": fused_compiles, "engine_compiles": engine_compiles,
        "compile_events": rec.compiles[-12:], "setup_phases": sysm_phases,
        "placement": placement, "by_agent": by_agent, "checked": sampled}
    if tr is not None:
        result["window"]["busy_by_device"] = tr.busy_by_device()
    if controls:
        result["control_checks"] = {}
        for c, got in control_numbers.items():
            c_checks, c_holds = judge(got, check["limits"])
            result["control_checks"][c] = {"checks": c_checks,
                                           "correct": c_holds}
    result["checks"] = checks
    return result


def emit(result: dict) -> None:
    """Numbers compared beside their limits (stderr, last lines), then the
    result line (stdout, last line)."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv, t_start: float) -> int:
    args = parse(argv)
    spec = loader.benchmark()
    cell, config, traffic = loader.cell(spec, args.workload)
    try:
        result = run_cell(spec, cell, config, traffic, args.seed,
                          args.seconds, bool(args.trace), t_start)
    except NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 3
    emit(result)
    return 0
