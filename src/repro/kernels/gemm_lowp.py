"""Fused quantized GEMM — the ladder's down-rungs, per-tile scales.

The paper measures the half-precision tensor-core trade (large speedup,
large precision loss) and notes the loss "can be considerably reduced at
the cost of increased computation".  This kernel pushes the input width
below bf16 — fp8 (e4m3) / int8 operands — and recovers accuracy the
Ootomo & Yokota way: carry the quantization RESIDUAL as a second
quantized operand and accumulate the cross terms in fp32.

Unlike the router-side qdq decomposition (``core.precision``: one
power-of-two scale per TENSOR), the fused kernel quantizes each
(bm, bk) / (bk, bn) tile in VMEM with its own arbitrary amax-derived
scale — finer granularity, so outlier rows only poison their own tile's
dynamic range.  Per tile-step:

    read f32 A,B tiles; amax-scale + quantize on the VPU;
    1 (naive) or 3 (error-corrected) MXU passes on the quantized terms;
    dequantize by sa*sb into ONE fp32 accumulator; ONE C write.

Quantized values ride fp32 carriers holding exact int8/e4m3 values: the
f32 dot then reproduces the int8 MXU's i32 accumulation exactly
(products <= 127^2, partial sums < 2^24 over any realistic bk) while
staying interpret-mode friendly.

Policies: fp8 / int8 (1 pass), fp8x3 / int8x3 (3 passes: lo.hi + hi.lo
+ hi.hi, the Eq. 3 drop-term shape applied to quantization error).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["gemm_lowp"]

_LOWP_POLICIES = ("fp8", "int8", "fp8x3", "int8x3")


def _quant_tile(x32, fmt: str):
    """Quantize one VMEM tile under its own amax-derived scale.

    Returns (q, s) with q an fp32 carrier of exact int8 / e4m3 values
    and x32 ~= q * s.  fp8 clips to the e4m3 max (448) BEFORE the cast:
    division rounding can push the top value a hair over, and e4m3fn
    turns overflow into nan rather than inf.
    """
    qmax = 127.0 if fmt == "int8" else 448.0
    amax = jnp.maximum(jnp.max(jnp.abs(x32)), jnp.float32(1e-30))
    s = amax / qmax
    y = x32 / s
    if fmt == "int8":
        q = jnp.clip(jnp.round(y), -qmax, qmax)
    else:
        q = jnp.clip(y, -qmax, qmax).astype(jnp.float8_e4m3fn)
        q = q.astype(jnp.float32)
    return q, s


def _lowp_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int, policy: str):
    """One (bm x bn) fp32 output tile; fused quantize + 1-3 MXU passes."""

    @pl.when(pl.program_id(2) == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    fmt = "int8" if policy.startswith("int8") else "fp8"
    a32 = a_ref[...].astype(jnp.float32)
    b32 = b_ref[...].astype(jnp.float32)
    qa, sa = _quant_tile(a32, fmt)                # VPU
    qb, sb = _quant_tile(b32, fmt)

    def mxu(x, y):
        return jnp.dot(x, y, preferred_element_type=jnp.float32)

    if policy.endswith("x3"):
        # residuals under their OWN (much smaller) scales; smallest-
        # magnitude terms summed first so fp32 loses the least
        qra, sra = _quant_tile(a32 - qa * sa, fmt)
        qrb, srb = _quant_tile(b32 - qb * sb, fmt)
        acc = mxu(qra, qb) * (sra * sb) + mxu(qa, qrb) * (sa * srb)
        acc_ref[...] += acc + mxu(qa, qb) * (sa * sb)
    else:
        acc_ref[...] += mxu(qa, qb) * (sa * sb)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("policy", "bm", "bn", "bk", "interpret")
)
def gemm_lowp(
    a: jax.Array,
    b: jax.Array,
    *,
    policy: str = "int8x3",
    bm: int = 256,
    bn: int = 256,
    bk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Fused quantized C = A @ B; fp32 in, fp32 out, per-tile scales."""
    if policy not in _LOWP_POLICIES:
        raise ValueError(f"policy {policy!r} not in {_LOWP_POLICIES}")
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

    kernel = functools.partial(_lowp_kernel, k_steps=k_steps, policy=policy)
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
