"""Ahead-of-time compiles for a described (not attached) TPU v5e chip.

The TPU compiler is installed with JAX, so these compile the main path's
kernels and one full-width decoder layer for ``v5e:2x2`` without a chip.
They catch what interpret mode cannot: block shapes the Mosaic tiling
rules refuse, kernels that silently lower without the compiled Pallas call,
and programs that do not fit the chip's 16 GB. Nothing runs, so they say
nothing about results or times.

The topology is described inside a module fixture, never while a module is
imported, and the tests skip where it cannot be described. The persistent
compilation cache is off around them: an entry written for a chip that is
not attached cannot be read back.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest

HBM_BYTES = 16 * 1024**3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("n,m", [(64, 128), (1024, 1024)])
def test_auction_bid_compiles_to_the_kernel(one_chip, n, m):
    from repro.kernels.auction_bid import auction_bid

    def bid(W, ask, ask2, active, eps):
        return auction_bid(W, ask, ask2, active, eps, bn=min(n, 128),
                           interpret=False)

    lowered = jax.jit(bid).lower(
        _spec(one_chip, (n, m), jnp.float32),
        _spec(one_chip, (m,), jnp.float32),
        _spec(one_chip, (m,), jnp.float32),
        _spec(one_chip, (n,), jnp.bool_),
        _spec(one_chip, (), jnp.float32))
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lcp_affinity_compiles_to_the_kernel(one_chip):
    from repro.kernels.lcp_affinity import lcp_affinity

    n, m, l = 64, 128, 1024
    compiled = jax.jit(lambda p, led: lcp_affinity(
        p, led, interpret=False)).lower(
        _spec(one_chip, (n, l), jnp.int32),
        _spec(one_chip, (n, m, l), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen3_8b_layer_prefill_fits_one_chip(one_chip):
    """One decoder layer of qwen3-8b at its published widths (GQA 32/8,
    head_dim 128, qk-norm, bf16) over a 512-token prompt bucket."""
    from repro.configs import get_config
    from repro.models import blocks

    cfg = dataclasses.replace(get_config("qwen3-8b"), n_layers=1)
    dtype = jnp.dtype(cfg.dtype)
    layer = jax.eval_shape(lambda k: blocks.attn_block_init(
        k, cfg, dtype, ffn_kind="dense"), jax.random.PRNGKey(0))
    layer = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), layer)

    def prefill_layer(p, x, lens):
        return blocks.attn_block_parallel(p, x, cfg, ffn_kind="dense",
                                          lens=lens)

    compiled = jax.jit(prefill_layer).lower(
        layer, _spec(one_chip, (1, 512, cfg.d_model), dtype),
        _spec(one_chip, (1,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES


@pytest.mark.parametrize("rows", [6, 96, 3072])
def test_moe_gmm_compiles_to_the_kernel(one_chip, rows):
    """The grouped expert kernel at DeepSeek-V2-Lite's widths (64 experts,
    hidden 2048, expert width 1408): a decode step's 6 rows, an extend's
    and a 512-token prefill's; the kernel is the program's ``moe_gmm``."""
    from repro.kernels.moe_gmm import moe_gmm

    e, d, f = 64, 2048, 1408
    compiled = jax.jit(lambda x, s, wg, wu, wd: moe_gmm(
        x, s, wg, wu, wd, interpret=False)).lower(
        _spec(one_chip, (rows, d), jnp.bfloat16),
        _spec(one_chip, (e,), jnp.int32),
        _spec(one_chip, (e, d, f), jnp.bfloat16),
        _spec(one_chip, (e, d, f), jnp.bfloat16),
        _spec(one_chip, (e, f, d), jnp.bfloat16)).compile()
    assert "%moe_gmm" in compiled.as_text()


def test_deepseek_v2_lite_moe_layer_prefill_fits_one_chip(one_chip,
                                                         monkeypatch):
    """One MoE layer of deepseek-v2-lite at its published widths (MLA with
    YaRN, 64 routed experts of width 1408 + 2 shared, bf16) over a
    512-token prompt bucket. The CPU backend would pick the kernel's
    interpret mode: the compiled kernel is asked for here."""
    from repro.configs import get_config
    from repro.kernels import moe_gmm
    from repro.models import blocks

    monkeypatch.setattr(moe_gmm, "resolve_interpret",
                        lambda interpret: bool(interpret))

    cfg = dataclasses.replace(get_config("deepseek-v2-lite-16b"), n_layers=2)
    dtype = jnp.dtype(cfg.dtype)
    layer = jax.eval_shape(lambda k: blocks.attn_block_init(
        k, cfg, dtype, ffn_kind="moe"), jax.random.PRNGKey(0))
    layer = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), layer)

    def prefill_layer(p, x, lens):
        return blocks.attn_block_parallel(p, x, cfg, ffn_kind="moe",
                                          lens=lens)

    compiled = jax.jit(prefill_layer).lower(
        layer, _spec(one_chip, (1, 512, cfg.d_model), dtype),
        _spec(one_chip, (1,), jnp.int32)).compile()
    assert "%moe_gmm" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES
