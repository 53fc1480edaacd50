"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs and no chip is needed: each test lowers one kernel at
gemma3-1b widths (head_dim 256, 4 query heads over 1 KV head, d_model
1152, d_ff 6912) and compiles it with the TPU compiler for one chip of a
``v5e:2x2`` topology.  That compiler refuses what the chip would refuse
and interpret mode accepts — block shapes off the (8, 128) tiling, too
much fast memory — so these tests guard the layouts interpret-mode
parity tests cannot.  Each compile takes about a second.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ops
from repro.core.ops import paged
from repro.kernels.attention_fused import flash_attention, flash_decode
from repro.kernels.attention_paged import flash_paged_decode
from repro.kernels.gemm_grouped import grouped_gemm

D_MODEL, D_FF, HEADS, KV_HEADS, HEAD_DIM = 1152, 6912, 4, 1, 256
GROUP = HEADS // KV_HEADS


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for an unattached chip is written to the persistent
    cache but cannot be read back here; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("window", [None, 512], ids=["causal", "window512"])
def test_flash_attention_forward(one_chip, window):
    q = _spec(one_chip, (1, 1024, KV_HEADS, GROUP, HEAD_DIM))
    k = _spec(one_chip, (1, 1024, KV_HEADS, HEAD_DIM))
    _assert_kernel_compiles(
        lambda q, k, v: flash_attention(q, k, v, window=window), q, k, k)


def test_flash_attention_grad(one_chip):
    """The fused backward (dq and dk/dv kernels) reads the forward's
    per-row log-sum-exp: its layout must tile in both directions."""
    q = _spec(one_chip, (2, 512, KV_HEADS, GROUP, HEAD_DIM))
    k = _spec(one_chip, (2, 512, KV_HEADS, HEAD_DIM))
    grad = jax.grad(lambda q, k, v: flash_attention(q, k, v).sum(),
                    argnums=(0, 1, 2))
    _assert_kernel_compiles(grad, q, k, k)


def test_flash_decode(one_chip):
    q = _spec(one_chip, (8, 1, KV_HEADS, GROUP, HEAD_DIM))
    cache = _spec(one_chip, (8, 2048, KV_HEADS, HEAD_DIM))
    pos = _spec(one_chip, (8,), jnp.int32)
    _assert_kernel_compiles(flash_decode, q, cache, cache, pos)


@pytest.mark.parametrize("kv_heads,quant", [(1, None), (4, "int8")],
                         ids=["bf16-kv1", "int8-kv4"])
def test_flash_paged_decode(one_chip, kv_heads, quant):
    """int8 pools carry per-(row, kv-head) scales; with more than one KV
    head their block must still tile."""
    cache = jax.eval_shape(lambda: paged.init_paged(
        8, 2048, kv_heads, HEAD_DIM, page_size=8,
        num_pages=8 * 2048 // 8 + 1, quant=quant))
    cache = jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype), cache)
    q = _spec(one_chip, (8, 1, kv_heads, GROUP, HEAD_DIM))
    pos = _spec(one_chip, (8,), jnp.int32)
    _assert_kernel_compiles(flash_paged_decode, q, cache, pos)


@pytest.mark.parametrize("policy", ["bf16", "refine_ab"])
def test_routed_pallas_gemm(one_chip, policy):
    a = _spec(one_chip, (2048, D_MODEL), jnp.float32)
    b = _spec(one_chip, (D_MODEL, D_FF), jnp.float32)
    _assert_kernel_compiles(
        lambda a, b: ops.gemm(a, b, policy=policy, backend="pallas",
                              interpret=False), a, b)


def test_grouped_gemm_forward(one_chip):
    x = _spec(one_chip, (2048, D_MODEL))
    w = _spec(one_chip, (8, D_MODEL, D_FF))
    offsets = _spec(one_chip, (9,), jnp.int32)
    _assert_kernel_compiles(grouped_gemm, x, w, offsets)


def test_latent_decode_tick_writes_the_cache_in_place(one_chip):
    """The deepseek-v2-lite serving cell's tick (6 layers, 8 of 64
    experts, 64 slots of 8192): its temporaries stay under one whole
    latent cache.  Scanned as xs/ys, or kept as one 576-wide leaf (which
    the TPU stores sequence-minor, so every row write relays it out),
    the cache was copied whole every tick: 6.6 and 4.4 GB of
    temporaries against 1.6."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import Segment, execution_policy_for
    from repro.runtime import serve_step

    cfg = dataclasses.replace(
        get_config("deepseek-v2-lite"), num_layers=6, num_experts=8,
        segments=(Segment(("mla", "mlp"), 1), Segment(("mla", "moe"), 5)))
    b, ctx = 64, 8192
    policy = execution_policy_for(cfg, require={"attention": ("decode",)})
    spec = lambda tree: jax.tree.map(
        lambda x: _spec(one_chip, x.shape, x.dtype), tree)
    cache = spec(serve_step.abstract_cache(cfg, b, ctx))
    vec = lambda dtype: _spec(one_chip, (b,), dtype)
    tick = jax.jit(serve_step.make_engine_tick(cfg, policy, eos_id=-1,
                                               max_ctx=ctx),
                   donate_argnums=(1, 2, 3, 4, 5))
    mem = tick.lower(spec(serve_step.abstract_params(cfg)), cache,
                     vec(jnp.int32), vec(jnp.int32), vec(bool),
                     vec(jnp.int32)).compile().memory_analysis()
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(cache))
    assert mem.temp_size_in_bytes < cache_bytes
    assert mem.alias_size_in_bytes >= cache_bytes
