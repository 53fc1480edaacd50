"""Naive MXU GEMM — the paper's "CUDA 9 WMMA, no shared memory" analogue.

The paper's Listing-1 kernel assigns one warp to one output tile and
streams operands straight from global memory; Fig. 6 shows it is *slower
than sgemm on CUDA cores*. The TPU translation of "no operand staging
discipline": a 2-D grid over output tiles where every program pulls its
FULL K-strips of A and B into VMEM at once — no K-blocking, no revisited
accumulator, no deep HBM->VMEM pipeline. For realistic K this blows the
VMEM budget (the analogue of the naive kernel's uncovered memory latency)
and forces tiny bm/bn, which is exactly why it loses to the tiled kernel.

Kept as a first-class backend so the benchmark harness can reproduce the
paper's naive-vs-tiled-vs-library comparison (Fig. 6) on TPU terms.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["gemm_naive"]


def _naive_kernel(a_ref, b_ref, o_ref):
    # Whole-K strips in VMEM; one MXU sweep; no accumulator revisit.
    o_ref[...] = jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "out_dtype", "interpret")
)
def gemm_naive(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """C = A @ B, one program per (bm x bn) tile, unblocked K."""
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {a.shape} x {b.shape}")
    bm, bn = min(bm, m), min(bn, n)
    if m % bm or n % bn:
        raise ValueError(f"(M,N)=({m},{n}) not divisible by ({bm},{bn})")

    a = a.astype(jnp.bfloat16)
    b = b.astype(jnp.bfloat16)

    return pl.pallas_call(
        _naive_kernel,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(a, b)
