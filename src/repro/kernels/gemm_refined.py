"""Fused precision-refined GEMM — the beyond-paper kernel.

The paper implements Eq. 3 as FOUR chained cuBLAS GEMM calls (Fig. 5) and
measures >4x the runtime of one GEMM, noting "there is room for a large
performance improvement". The fusion opportunity is structural:

  unfused (paper):  4x { read A-tile, read B-tile, read+write C } passes
  fused (here):     1x { read A,B f32 tiles; split on the VPU;
                         2-4 MXU passes on the in-register/VMEM terms;
                         ONE fp32 accumulator; ONE C write }

Per (bm, bn, bk) tile-step the fused kernel moves 2x the bytes of one
bf16 pass (f32 operands) instead of 4x (four bf16 passes) and writes C
once instead of 4 times — so refine_ab costs ~2x a plain bf16 GEMM in
HBM traffic while doing 4x the MXU work. Since large-GEMM is
compute-bound on TPU (arithmetic intensity >> ridge point), the fused
refined GEMM lands at ~n_passes x the compute time with *no* extra
memory-bound tax, vs the paper's ~5x wall-clock for 4x compute.

The VPU split (bf16 round + subtract) runs on vector units while the MXU
does matmuls — the TPU-native version of the paper's suggestion to use
"CUDA cores and Tensor Cores concurrently".

Policies: refine_a (Eq. 2, 2 passes), bf16x3 (Eq. 3 minus the O(eps^2)
RA.RB term, 3 passes), refine_ab (Eq. 3, 4 passes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["gemm_refined"]

_POLICY_PASSES = {"refine_a": 2, "bf16x3": 3, "refine_ab": 4}


def _split2(x32):
    hi = x32.astype(jnp.bfloat16)
    lo = (x32 - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _refined_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int, policy: str):
    """One (bm x bn) fp32 output tile; fused split + multi-pass MXU."""

    @pl.when(pl.program_id(2) == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a32 = a_ref[...].astype(jnp.float32)
    b32 = b_ref[...].astype(jnp.float32)
    a_hi, a_lo = _split2(a32)                     # VPU

    def mxu(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)

    if policy == "refine_a":
        b_hi = b32.astype(jnp.bfloat16)           # Eq. 2: B rounded only
        acc_ref[...] += mxu(a_lo, b_hi) + mxu(a_hi, b_hi)
    else:
        b_hi, b_lo = _split2(b32)                 # VPU
        acc = mxu(a_lo, b_hi) + mxu(a_hi, b_lo)   # first-order terms
        if policy == "refine_ab":                 # Eq. 3's O(eps^2) term
            acc += mxu(a_lo, b_lo)
        acc_ref[...] += acc + mxu(a_hi, b_hi)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("policy", "bm", "bn", "bk", "interpret")
)
def gemm_refined(
    a: jax.Array,
    b: jax.Array,
    *,
    policy: str = "refine_ab",
    bm: int = 256,
    bn: int = 256,
    bk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Fused refined C = A @ B; fp32 in, fp32 out, 2-4 MXU passes/tile.

    VMEM working set at defaults: f32 a/b tiles 256 KiB each, their four
    bf16 halves 128 KiB each transiently, fp32 acc 256 KiB -> ~1.3 MiB,
    still deep-pipeline friendly on a 16 MiB VMEM.
    """
    if policy not in _POLICY_PASSES:
        raise ValueError(f"policy {policy!r} not in {sorted(_POLICY_PASSES)}")
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {a.shape} x {b.shape}")
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    if m % bm or n % bn or k % bk:
        raise ValueError(f"({m},{n},{k}) not divisible by ({bm},{bn},{bk})")
    k_steps = k // bk

    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)

    kernel = functools.partial(_refined_kernel, k_steps=k_steps, policy=policy)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a, b)
