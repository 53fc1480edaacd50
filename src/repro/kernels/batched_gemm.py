"""Batched small-matrix GEMM — the paper's Fig. 7 workload, TPU-adapted.

The paper batches 16x16 matmuls by assigning one warp (one Tensor Core
op) per matrix and reaches 4 Tflops/s — 3% of device peak — because a
16x16x16 MMA leaves the rest of the machine idle; the win (2.5-12x over
batched sgemm) comes purely from narrow precision and parallel occupancy.

A 16x16 matmul on a 128x128 MXU occupies 1/64th of the systolic array,
so the one-matrix-per-op mapping has no TPU future. Instead we PACK:

  pack p = tile/n matrices block-diagonally into one (tile x tile) MXU
  operand pair; their product is block-diagonal with the p small results.

One MXU pass then computes p small matmuls (p=8 for n=16 at tile=128):
8x the naive mapping's utilization — the same improvement band the paper
measured over batched sgemm, but obtained structurally rather than from
precision alone. Utilization caps at p/tile = n/tile of peak (12.5% for
16/128) because the off-diagonal MXU work is masked waste; that cap is
the TPU analogue of the paper's 4-of-125 Tflops observation, and both
are reported by the Fig. 7 benchmark.

Layout: operands arrive as (G, n, n). The wrapper reshapes to groups of
p and the kernel scatters each group into a block-diagonal (tile x tile)
VMEM scratch pair, runs one MXU pass, and slices the diagonal blocks back
out. The naive one-matrix-per-grid-step variant is kept for comparison.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["batched_gemm", "batched_gemm_naive"]


def _packed_kernel(a_ref, b_ref, o_ref, pa_ref, pb_ref, *, pack: int, n: int):
    """a_ref/b_ref: (1, pack, n, n) group -> o_ref: (1, pack, n, n)."""
    # Scatter the group into block-diagonal (pack*n, pack*n) operands.
    pa_ref[...] = jnp.zeros_like(pa_ref)
    pb_ref[...] = jnp.zeros_like(pb_ref)
    for i in range(pack):  # static unroll: pack is a compile-time constant
        pa_ref[i * n:(i + 1) * n, i * n:(i + 1) * n] = a_ref[0, i]
        pb_ref[i * n:(i + 1) * n, i * n:(i + 1) * n] = b_ref[0, i]
    # One MXU pass computes all `pack` products on the diagonal.
    prod = jnp.dot(pa_ref[...], pb_ref[...], preferred_element_type=jnp.float32)
    for i in range(pack):
        o_ref[0, i] = prod[i * n:(i + 1) * n, i * n:(i + 1) * n]


@functools.partial(
    jax.jit, static_argnames=("tile", "groups_per_step", "interpret")
)
def batched_gemm(
    a: jax.Array,
    b: jax.Array,
    *,
    tile: int = 128,
    groups_per_step: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """(G, n, n) x (G, n, n) -> (G, n, n) fp32, block-diagonal MXU packing.

    Requires n | tile and pack | G (wrappers in ops.py pad G).
    """
    g, n, n2 = a.shape
    if n != n2 or a.shape != b.shape:
        raise ValueError(f"expected matching (G, n, n); got {a.shape}, {b.shape}")
    if tile % n:
        raise ValueError(f"n={n} must divide MXU tile={tile}")
    pack = tile // n
    if g % pack:
        raise ValueError(f"G={g} must be a multiple of pack={pack} (pad in ops.py)")

    a = a.astype(jnp.bfloat16).reshape(g // pack, pack, n, n)
    b = b.astype(jnp.bfloat16).reshape(g // pack, pack, n, n)

    kernel = functools.partial(_packed_kernel, pack=pack, n=n)
    out = pl.pallas_call(
        kernel,
        grid=(g // pack,),
        in_specs=[
            pl.BlockSpec((1, pack, n, n), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, pack, n, n), lambda i: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, pack, n, n), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g // pack, pack, n, n), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((tile, tile), jnp.bfloat16),
            pltpu.VMEM((tile, tile), jnp.bfloat16),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret,
    )(a, b)
    return out.reshape(g, n, n)


def _naive_kernel(a_ref, b_ref, o_ref):
    o_ref[0] = jnp.dot(a_ref[0], b_ref[0], preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def batched_gemm_naive(
    a: jax.Array, b: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """One small matmul per grid step — the paper's one-warp-per-matrix
    mapping, kept as the utilization baseline for Fig. 7."""
    g, n, n2 = a.shape
    if n != n2 or a.shape != b.shape:
        raise ValueError(f"expected matching (G, n, n); got {a.shape}, {b.shape}")
    a = a.astype(jnp.bfloat16)
    b = b.astype(jnp.bfloat16)
    return pl.pallas_call(
        _naive_kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, n, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, n, n), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n, n), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, n, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(a, b)
