"""Kernel micro-benchmarks (CPU): Pallas interpret-mode correctness-path
timings vs the pure-jnp oracles + the batched-LCP affinity fast path vs the
python ledger loop (the beyond-paper router speedup, §Perf).

NOTE: interpret-mode timings are NOT TPU performance — kernels are validated
here and *profiled structurally* via the dry-run (EXPERIMENTS.md §Roofline).
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, start, timed
from repro.core.affinity import PrefixLedger
from repro.utils.timing import bench_call


def run():
    rng = np.random.default_rng(0)
    # batched LCP vs python-loop ledger (router hot loop)
    led = PrefixLedger()
    agents = [f"a{i}" for i in range(16)]
    prompts, dialogues = [], []
    for j in range(32):
        d = f"d{j}"
        dialogues.append(d)
        base = rng.integers(1, 250, size=192).astype(np.int32)
        prompts.append(base)
        for i, a in enumerate(agents):
            if (i + j) % 2 == 0:
                led.update(a, d, base[: rng.integers(10, 190)])
    t_py = bench_call(lambda: led.affinity_matrix(prompts, dialogues, agents),
                      warmup=1, iters=3, block=False)
    t_kr = bench_call(lambda: led.affinity_matrix(prompts, dialogues, agents,
                                                  use_kernel=True),
                      warmup=1, iters=3, block=False)
    emit("kernels/lcp_affinity_32x16", t_kr,
         f"python_us={t_py:.0f} batched_us={t_kr:.0f} "
         f"speedup={t_py / max(t_kr, 1):.1f}x")

    # flash attention interpret vs jnp oracle (correctness-path timing)
    import jax.numpy as jnp

    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ref import attention_ref

    q = jnp.asarray(rng.standard_normal((1, 256, 8, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 256, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 256, 2, 64)), jnp.float32)
    t_ref = bench_call(lambda: attention_ref(q, k, v), warmup=1, iters=3)
    t_pal = bench_call(lambda: flash_attention(q, k, v), warmup=1, iters=3)
    err = float(jnp.max(jnp.abs(flash_attention(q, k, v)
                                - attention_ref(q, k, v))))
    emit("kernels/flash_attn_256", t_pal,
         f"jnp_oracle_us={t_ref:.0f} interpret_us={t_pal:.0f} "
         f"max_err={err:.1e}")

    # auction bidding round: interpret-mode kernel vs jnp oracle (bit-equal)
    from repro.kernels.auction_bid import auction_bid
    from repro.kernels.ref import auction_bid_ref

    B = jnp.asarray(np.maximum(rng.uniform(-1, 4, (256, 384)), 0.0),
                    jnp.float32)
    ask = np.asarray(rng.uniform(0, 2, 384), np.float32)
    ask2 = ask + np.asarray(rng.uniform(0, 1, 384), np.float32)
    # ~20% single-unit agents: ask2 quotes the +big sentinel
    one_unit = rng.random(384) < 0.2
    ask2[one_unit] = np.float32(np.finfo(np.float32).max / 4)
    ask, ask2 = jnp.asarray(ask), jnp.asarray(ask2)
    active = jnp.asarray(rng.random(256) > 0.25)
    t_ref = bench_call(lambda: auction_bid_ref(B, ask, ask2, active, 0.01),
                       warmup=1, iters=3)
    t_pal = bench_call(lambda: auction_bid(B, ask, ask2, active, 0.01),
                       warmup=1, iters=3)
    got = auction_bid(B, ask, ask2, active, 0.01)
    want = auction_bid_ref(B, ask, ask2, active, 0.01)
    exact = all(bool(jnp.array_equal(g, w)) for g, w in zip(got, want))
    emit("kernels/auction_bid_256x384", t_pal,
         f"jnp_oracle_us={t_ref:.0f} interpret_us={t_pal:.0f} "
         f"bit_equal={exact}")

    from repro.kernels.ref import wkv6_ref
    from repro.kernels.wkv6 import wkv6

    r = jnp.asarray(rng.standard_normal((1, 64, 4, 32)), jnp.float32)
    kk = jnp.asarray(rng.standard_normal((1, 64, 4, 32)), jnp.float32)
    vv = jnp.asarray(rng.standard_normal((1, 64, 4, 32)), jnp.float32)
    lw = jnp.clip(jnp.asarray(-np.exp(rng.standard_normal((1, 64, 4, 32))),
                              jnp.float32), -4, -1e-3)
    u = jnp.asarray(rng.standard_normal((4, 32)), jnp.float32)
    s0 = np.zeros((1, 4, 32, 32), np.float32)
    t_ref = bench_call(lambda: wkv6_ref(r, kk, vv, lw, u, s0), warmup=1, iters=3)
    t_pal = bench_call(lambda: wkv6(r, kk, vv, lw, u), warmup=1, iters=3)
    emit("kernels/wkv6_64", t_pal,
         f"stepwise_oracle_us={t_ref:.0f} chunked_interpret_us={t_pal:.0f}")


if __name__ == "__main__":
    start()
    run()
