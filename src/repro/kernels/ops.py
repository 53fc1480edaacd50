"""DEPRECATED jit'd dispatch wrappers over the Pallas kernels — thin
shims over the op registry in ``repro.core.ops``.

Backends mirror the paper's three programming interfaces:

  backend="xla"          -> jax.lax dots (the cuBLAS analogue: vendor path)
  backend="pallas"       -> gemm_tiled / gemm_refined (the CUTLASS analogue)
  backend="pallas_naive" -> gemm_naive (the raw-WMMA analogue)

The same registry serves the model stack (``peinsum`` routes) and the
benchmarks, so models and benchmarks measure the identical code path.
Off the TPU, Pallas kernels execute via ``interpret=True`` (resolved
once from the default backend); on TPU they compile through Mosaic.
Tile shapes come from the shape-keyed cache in core.ops unless
the caller pins them; padding to block multiples happens in the router
so arbitrary shapes work everywhere.

New code should call ``repro.core.ops.gemm`` directly; ``gemm`` here
emits a ``DeprecationWarning``.  ``gemm_batched`` (the Fig.-7 packed
many-small-GEMM path) has no registry family yet and stays the
canonical entry point.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from repro.core import ops
from repro.core.ops import default_interpret
from repro.kernels.batched_gemm import batched_gemm, batched_gemm_naive

__all__ = ["gemm", "gemm_batched", "default_interpret"]


def gemm(
    a: jax.Array,
    b: jax.Array,
    *,
    policy: str = "bf16",
    backend: str = "pallas",
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """DEPRECATED: use ``repro.core.ops.gemm``.

    Policy-routed C = A @ B through a selectable backend; tile shapes
    default to the shape-keyed cache (bm/bn/bk override it — including
    the ``pallas_naive`` K padding, which historically ignored bk),
    shapes are padded up to block multiples and the result is sliced
    back; fp32 out always (the accumulator type).
    """
    warnings.warn("repro.kernels.ops.gemm is deprecated; use "
                  "repro.core.ops.gemm", DeprecationWarning, stacklevel=2)
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise ValueError(f"gemm expects (m,k) x (k,n); got {a.shape} x {b.shape}")
    tiles = None
    if bm is not None or bn is not None or bk is not None:
        base = ops.tile_for(backend, a.shape[0], b.shape[1], a.shape[1])
        tiles = ops.TileConfig(bm=bm or base.bm, bn=bn or base.bn,
                               bk=bk or base.bk)
    return ops.gemm(a, b, policy=policy, backend=backend, tiles=tiles,
                    interpret=interpret)


def gemm_batched(
    a: jax.Array,
    b: jax.Array,
    *,
    backend: str = "pallas",
    tile: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Batched (G, n, n) small GEMMs; pads G to the packing multiple."""
    if a.ndim != 3 or a.shape != b.shape or a.shape[1] != a.shape[2]:
        raise ValueError(f"expected matching (G, n, n); got {a.shape}, {b.shape}")
    g, n, _ = a.shape
    interp = default_interpret() if interpret is None else interpret

    if backend == "xla":
        return jax.lax.dot_general(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    if backend == "pallas_naive":
        return batched_gemm_naive(a, b, interpret=interp)

    if backend != "pallas":
        raise ValueError(f"unknown backend {backend!r}")

    pack = tile // n
    if pack == 0:
        # n > tile: nothing to pack — the packing kernel is built for
        # MANY-small problems (paper §V). Large per-problem GEMMs route
        # to the vendor (XLA) batched path instead of dividing by zero.
        return gemm_batched(a, b, backend="xla", tile=tile,
                            interpret=interpret)
    pad = (-g) % pack
    if pad:
        a = jnp.concatenate([a, jnp.zeros((pad, n, n), a.dtype)], axis=0)
        b = jnp.concatenate([b, jnp.zeros((pad, n, n), b.dtype)], axis=0)
    out = batched_gemm(a, b, tile=tile, interpret=interp)
    return out[:g]
