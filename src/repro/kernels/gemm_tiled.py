"""Tiled mixed-precision GEMM — the CUTLASS / "WMMA + shared memory"
analogue of the paper, as a Pallas TPU kernel.

The paper's central performance finding (Fig. 6) is that the naive
per-warp WMMA kernel gets *zero* speedup from Tensor Cores while the
shared-memory-tiled version gets ~5x and cuBLAS ~7x: the matrix unit is
useless unless operand tiles are staged through fast memory. The TPU
translation: stage (bm x bk) / (bk x bn) operand tiles through VMEM with
an fp32 VMEM accumulator, MXU-aligned block shapes (multiples of 128 on
the lane dim, 8/16 on sublanes), and a 3-D grid whose innermost dimension
walks K so Pallas double-buffers the HBM->VMEM streams.

Grid: (M/bm, N/bn, K/bk), dimension order chosen so the K walk is the
innermost ("arbitrary") axis and the output block is revisited across it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["gemm_tiled"]


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    """One (bm x bn) output tile; accumulates over the K grid axis."""

    @pl.when(pl.program_id(2) == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU pass: bf16 x bf16 -> fp32 accumulate.
    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _check_tiles(m: int, n: int, k: int, bm: int, bn: int, bk: int) -> None:
    for dim, blk, name in ((m, bm, "M"), (n, bn, "N"), (k, bk, "K")):
        if dim % blk != 0:
            raise ValueError(
                f"{name}={dim} not divisible by block {blk}; pad operands "
                f"(tests exercise the padded wrapper in ops.py)")


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "out_dtype", "interpret"),
)
def gemm_tiled(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 256,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """C = A @ B with bf16 MXU passes and an fp32 VMEM accumulator.

    a: (M, K) any float dtype (cast to bf16 on the way in)
    b: (K, N)
    Default 256^3 blocks: VMEM working set = a-tile 128 KiB + b-tile
    128 KiB + fp32 acc 256 KiB (+ double buffering on the streamed
    operands) ~= 0.8 MiB of ~16 MiB/core — small enough to let the
    pipeline run deep, large enough for full MXU occupancy.
    """
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {a.shape} x {b.shape}")
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    _check_tiles(m, n, k, bm, bn, bk)
    k_steps = k // bk

    a = a.astype(jnp.bfloat16)
    b = b.astype(jnp.bfloat16)

    kernel = functools.partial(_gemm_kernel, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a, b)
