"""Grouped ragged expert-GEMM Pallas kernel family (forward + backward).

The paper's batched-GEMM experiment (Fig. 7) is where Tensor Cores lose
the most headroom — 4 of 125 Tflops/s — because many small independent
matmuls leave the matrix unit idle.  Our MoE expert FFN is exactly that
shape: E medium GEMMs whose per-expert row counts are *data dependent*
(the router decides), which the capacity-padded dispatch turns into E
equal worst-case GEMM launches with mostly-empty rows.  This module is
the occupancy fix: ONE kernel walks a single token dimension sorted by
expert, so the MXU sees one dense streaming GEMM whose weight operand
switches per tile.

Layout contract
---------------
Tokens are pre-sorted by expert into a flat (N, D) buffer whose
per-expert regions are aligned to the row-tile size ``bm``:

    rows [offsets[e], offsets[e+1])   belong to expert e,
    offsets[0] = 0, interior offsets multiples of bm,
    rows past a group's real token count (and past offsets[E]) are ZERO.

Every row tile therefore belongs to exactly ONE expert.  The (E+1,)
``group_offsets`` vector is the only dynamic metadata: the wrapper
derives a per-tile group-id vector from it and *scalar-prefetches* it
(``PrefetchScalarGridSpec``), so the weight BlockSpec index map selects
expert ``gids[i]``'s weight block while the token tile streams — no
gather, no (E, C, D) dispatch tensor, no host round trip.  Tiles past
``offsets[E]`` carry the dead-group id E and are skipped (their output
is written as zeros without issuing MXU passes) — the grouped analogue
of the flash kernels' masked-block skipping.

Precision ladder
----------------
The in-kernel contraction honors the full PrecisionPolicy ladder
(``core.precision`` Eq. 1-3): operands are split on the VPU into bf16
(hi, lo[, mid]) terms and each term pair runs as one bf16-input /
fp32-accumulate MXU pass, summed smallest-magnitude-first — the same
fused-refinement structure as ``gemm_refined``, applied per expert tile.

Backward
--------
A custom VJP keeps training on the fused path:

    dx = grouped GEMM of the cotangent against TRANSPOSED weights
         (same kernel, contraction flipped onto w's output dim);
    dw = per-group accumulation over the sorted token runs — the token
         walk is the innermost grid axis, an accumulator is zeroed at
         each group's first tile and flushed to dw[e] at its last
         (group runs are contiguous because tokens are sorted).

Both backward contractions run the same policy ladder as the forward.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import precision as prec

__all__ = ["GroupedConfig", "grouped_gemm", "tile_group_ids"]


@dataclasses.dataclass(frozen=True)
class GroupedConfig:
    """Static description of one grouped-GEMM problem (hashable, so it
    rides through ``jax.custom_vjp`` nondiff_argnums as ONE argument)."""

    num_groups: int
    precision: str = "bf16"            # core.precision policy name
    bm: int = 128                      # token-row tile (the group align)
    bn: int = 128                      # output-column tile
    bk: int = 128                      # contraction tile
    interpret: bool = False


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _policy_dot(x, y, policy: str, dims: tuple[tuple[int, ...],
                                               tuple[int, ...]]):
    """fp32 x fp32 -> fp32 dot under the precision-policy ladder.

    One MXU pass per ``policy_terms`` pair (bf16 operands, fp32
    accumulate), summed smallest-magnitude-first; ``f32`` runs a single
    full-precision pass.  ``dims`` are plain dot_general contracting
    dims — the forward contracts (1,)x(0,), dx (1,)x(1,) (w transposed
    onto its output dim), dw (0,)x(0,) (token-run reduction).
    """
    dnums = (dims, ((), ()))

    def one(a, b):
        return jax.lax.dot_general(a, b, dnums,
                                   preferred_element_type=jnp.float32)

    if policy == "f32":
        return one(x.astype(jnp.float32), y.astype(jnp.float32))
    x_terms, y_terms = prec.operand_terms(x, y, policy)
    out = None
    for tx, ty in prec.policy_terms(policy):
        part = one(x_terms[tx], y_terms[ty])
        out = part if out is None else out + part
    assert out is not None
    return out


def tile_group_ids(group_offsets: jax.Array, n_rows: int,
                   bm: int) -> jax.Array:
    """(nt,) group id per row tile; dead tiles (past offsets[-1]) get E.

    Well defined because interior offsets are bm-multiples: each tile
    intersects exactly one group's region.  Zero-width groups (possible
    through the public contract, not through the MoE dispatch, which
    aligns every group to >= one tile) never claim a tile.
    """
    starts = jnp.arange(_round_up(n_rows, bm) // bm, dtype=jnp.int32) * bm
    return (jnp.searchsorted(group_offsets.astype(jnp.int32), starts,
                             side="right") - 1).astype(jnp.int32)


# ================================================================ kernels

def _gmm_kernel(gids_ref, x_ref, w_ref, o_ref, acc_ref, *,
                cfg: GroupedConfig, n_k: int, trans_w: bool):
    """One (bm x bn) output tile of x @ w[g] (or x @ w[g].T for dx),
    accumulated over the contraction grid axis; dead tiles skip the MXU
    passes and store zeros."""
    i, kk = pl.program_id(0), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(gids_ref[i] < cfg.num_groups)
    def _step():
        x = x_ref[...].astype(jnp.float32)
        w = w_ref[0].astype(jnp.float32)
        dims = ((1,), (1,)) if trans_w else ((1,), (0,))
        acc_ref[...] += _policy_dot(x, w, cfg.precision, dims)

    @pl.when(kk == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _gmm_call(cfg: GroupedConfig, x, w, gids, *, trans_w: bool):
    """x: (N, K) row-padded; w: (E, K, M) (or (E, M, K) when trans_w);
    all dims already tile multiples.  Returns (N, M) fp32."""
    n_rows, k = x.shape
    m = w.shape[1] if trans_w else w.shape[2]
    bm, bn, bk = cfg.bm, min(cfg.bn, m), min(cfg.bk, k)
    nt, n_n, n_k = n_rows // bm, m // bn, k // bk
    e_last = cfg.num_groups - 1

    if trans_w:
        w_spec = pl.BlockSpec(
            (1, bn, bk),
            lambda i, j, kk, g: (jnp.minimum(g[i], e_last), j, kk))
    else:
        w_spec = pl.BlockSpec(
            (1, bk, bn),
            lambda i, j, kk, g: (jnp.minimum(g[i], e_last), kk, j))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt, n_n, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk, g: (i, kk)),
            w_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, g: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    kernel = functools.partial(_gmm_kernel, cfg=cfg, n_k=n_k,
                               trans_w=trans_w)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, m), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=cfg.interpret,
    )(gids, x, w)


def _dw_kernel(gids_ref, x_ref, dy_ref, dw_ref, acc_ref, *,
               cfg: GroupedConfig, n_t: int):
    """dw[g] accumulation over the sorted token runs: the token walk is
    the innermost ("arbitrary") grid axis; the accumulator is zeroed at
    each group's FIRST tile and flushed at its LAST — group runs are
    contiguous because tokens are sorted by expert."""
    i = pl.program_id(2)
    g = gids_ref[i]
    live = g < cfg.num_groups
    first = (i == 0) | (gids_ref[jnp.maximum(i - 1, 0)] != g)
    last = (i == n_t - 1) | (gids_ref[jnp.minimum(i + 1, n_t - 1)] != g)

    @pl.when(live & first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _step():
        x = x_ref[...].astype(jnp.float32)
        dy = dy_ref[...].astype(jnp.float32)
        acc_ref[...] += _policy_dot(x, dy, cfg.precision, ((0,), (0,)))

    @pl.when(live & last)
    def _flush():
        dw_ref[0] = acc_ref[...].astype(dw_ref.dtype)


def _dw_call(cfg: GroupedConfig, x, dy, gids):
    """x: (N, K), dy: (N, M), tile-multiple dims -> dw (E, K, M) fp32.

    Groups with no live tile (zero-width regions) leave their block
    unwritten; the VJP wrapper masks those to zero.
    """
    n_rows, k = x.shape
    m = dy.shape[1]
    bm, bn, bk = cfg.bm, min(cfg.bn, m), min(cfg.bk, k)
    nt = n_rows // bm
    e_last = cfg.num_groups - 1

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k // bk, m // bn, nt),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda d, f, i, g: (i, d)),
            pl.BlockSpec((bm, bn), lambda d, f, i, g: (i, f)),
        ],
        out_specs=pl.BlockSpec(
            (1, bk, bn),
            lambda d, f, i, g: (jnp.minimum(g[i], e_last), d, f)),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
    )
    kernel = functools.partial(_dw_kernel, cfg=cfg, n_t=nt)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((cfg.num_groups, k, m),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=cfg.interpret,
    )(gids, x, dy)


# ====================================================== padding + custom VJP

def _pad2d(x, rows: int, cols: int):
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x


def _padded_shapes(cfg: GroupedConfig, n: int, d: int, f: int):
    # D and F swap contraction/output roles between the forward and the
    # dx/dw backward kernels, so BOTH are padded to a common quantum
    # every tile size divides — otherwise a bk > bn backward walk would
    # floor away the remainder columns of the cotangent.
    q = math.lcm(cfg.bn, cfg.bk, 128)
    return _round_up(n, cfg.bm), _round_up(d, q), _round_up(f, q)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _grouped(cfg: GroupedConfig, x, w, gids):
    return _grouped_fwd(cfg, x, w, gids)[0]


def _grouped_fwd(cfg: GroupedConfig, x, w, gids):
    n, d = x.shape
    f = w.shape[2]
    n_p, d_p, f_p = _padded_shapes(cfg, n, d, f)
    xp = _pad2d(x, n_p, d_p)
    wp = jnp.pad(w, ((0, 0), (0, d_p - d), (0, f_p - f)))
    out = _gmm_call(cfg, xp, wp, gids, trans_w=False)
    return out[:n, :f], (x, w, gids)


def _grouped_bwd(cfg: GroupedConfig, res, g):
    x, w, gids = res
    n, d = x.shape
    f = w.shape[2]
    n_p, d_p, f_p = _padded_shapes(cfg, n, d, f)
    xp = _pad2d(x.astype(jnp.float32), n_p, d_p)
    wp = jnp.pad(w.astype(jnp.float32),
                 ((0, 0), (0, d_p - d), (0, f_p - f)))
    gp = _pad2d(g.astype(jnp.float32), n_p, f_p)
    # dx: the same grouped walk against transposed weights (dims flip
    # the contraction onto w's output dim; no materialized transpose).
    dx = _gmm_call(cfg, gp, wp, gids, trans_w=True)[:n, :d]
    # dw: per-group accumulation over the sorted token runs.
    dw = _dw_call(cfg, xp, gp, gids)[:, :d, :f]
    # Zero-width groups own no tile, so their dw block is never written
    # (uninitialized memory on hardware — select, don't multiply, so a
    # NaN/Inf bit pattern there cannot leak through as 0 * NaN).
    written = jax.nn.one_hot(gids, cfg.num_groups,
                             dtype=jnp.float32).max(axis=0)
    dw = jnp.where(written[:, None, None] > 0, dw, 0.0)
    return dx.astype(x.dtype), dw.astype(w.dtype), None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_gemm(x: jax.Array, w: jax.Array, group_offsets: jax.Array, *,
                 precision: str = "bf16", bm: int = 128, bn: int = 128,
                 bk: int = 128, interpret: bool = False) -> jax.Array:
    """Ragged grouped GEMM: out[r] = x[r] @ w[e] for r in group e's rows.

    x: (N, D) rows sorted by group in the aligned layout (module
    docstring): group e occupies [offsets[e], offsets[e+1]), interior
    offsets are multiples of ``bm``, padding rows are zero.
    w: (E, D, F); group_offsets: (E+1,) int32.  Returns (N, F) fp32
    (padding rows come back zero).  Differentiable via the fused dx/dw
    Pallas backward kernels.
    """
    if x.ndim != 2 or w.ndim != 3 or x.shape[1] != w.shape[1]:
        raise ValueError(
            f"grouped_gemm expects (N,D) x (E,D,F); got {x.shape} x {w.shape}")
    if group_offsets.shape != (w.shape[0] + 1,):
        raise ValueError(
            f"group_offsets must be (E+1,)={w.shape[0] + 1}; "
            f"got {group_offsets.shape}")
    cfg = GroupedConfig(num_groups=w.shape[0], precision=precision,
                        bm=min(bm, _round_up(x.shape[0], 8)), bn=bn, bk=bk,
                        interpret=interpret)
    gids = tile_group_ids(group_offsets, x.shape[0], cfg.bm)
    return _grouped(cfg, x, w, gids)
