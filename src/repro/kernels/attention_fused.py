"""Fused flash-attention Pallas kernel family (forward, decode, backward).

The paper's headline result is that the 7x-over-fp32 win comes from
FUSING the multiply-and-accumulate stages of a mixed-precision pipeline
into one unit (WMMA fragments staged through shared memory, CUTLASS
fused epilogues) instead of chaining vendor GEMM calls with materialized
intermediates.  Our attention path was the last place the framework
still paid the unfused tax: two routed GEMMs (QK^T, then PV) with a
materialized (B, H, Sq, Skv) fp32 score tensor between them.  This
module is the fused counterpart — the score tile never leaves VMEM.

Online-softmax tiling
---------------------
The kernel walks the KV sequence in (block_kv)-sized tiles for each
(batch, head, q-block) grid cell, carrying three VMEM-resident
accumulators across the walk:

    m   (block_q,)  running row max of the scores seen so far
    l   (block_q,)  running sum of exp(score - m)
    acc (block_q, head_dim)  UNNORMALIZED output accumulator

For each KV tile: s = q k^T is computed on the MXU (policy-decomposed,
see below), masked (causal / sliding-window / tail padding), and folded
into the running statistics with the standard correction factor
``alpha = exp(m_old - m_new)``:

    m_new = max(m, rowmax(s));  p = exp(s - m_new)
    l     = l * alpha + rowsum(p)
    acc   = acc * alpha + p @ v

The final normalization ``acc / l`` happens once, on the last KV tile,
together with the log-sum-exp residual ``lse = m + log(l)`` that the
backward pass consumes.  The (block_q, block_kv) score tile lives only
in VMEM/registers — the HBM traffic of the two-GEMM path's (B,H,Sq,Skv)
round trip is gone, which is exactly the fusion the paper measures.

Precision ladder
----------------
Both in-kernel contractions (QK^T and the value contraction PV) honor
the PrecisionPolicy ladder: operands are split on the VPU into bf16
(hi, lo[, mid]) terms per ``core.precision`` Eq. 1-3 and each term pair
runs as one bf16-input/fp32-accumulate MXU pass, summed
smallest-magnitude-first — the same fused-refinement structure as
``gemm_refined``, applied to attention.  ``refine_a`` etc. therefore
buy a refined pass on the value contraction (p is fp32 in-kernel, its
bf16 rounding residual is carried as a second MXU pass) without ever
materializing p in HBM.

GQA / decode
------------
Query heads are laid out head-major as (kv_head * group + g) and the
K/V BlockSpec index maps divide by ``group``, so grouped-query heads
share one K/V tile stream without materializing repeated K/V.  The
decode variant reads the ring-buffer/linear KV cache at a PER-ROW
position vector (scalar-prefetched), reproducing the serve engine's
continuous-batching mask: slot j of a ring of size S holds absolute
position ``pos - ((pos - j) mod S)``.

The custom VJP keeps training on the fused path: dq and dk/dv are two
more Pallas kernels that recompute the score tile from (q, k) and the
saved ``lse`` (flash-attention backward), with the same policy-split
contractions — so the backward runs on the same backend the forward
ran, as for the routed GEMMs.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import precision as prec

__all__ = ["FlashConfig", "flash_attention", "flash_decode"]

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class FlashConfig:
    """Static description of one fused-attention problem.

    Hashable so it can ride through ``jax.custom_vjp`` nondiff_argnums
    and ``functools.partial``-ed kernels as ONE static argument.
    """

    causal: bool = True
    window: int | None = None          # sliding window (causal only)
    softcap: float | None = None       # s <- cap * tanh(s / cap)
    precision: str = "bf16"            # core.precision policy name
    block_q: int = 128
    block_kv: int = 128
    interpret: bool = False


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


# ------------------------------------------------------- policy MXU dots

def _policy_dot(x, y, policy: str, *, trans_y: bool = False):
    """fp32 x fp32 -> fp32 dot under the precision-policy ladder.

    One MXU pass per ``policy_terms`` pair (bf16 operands, fp32
    accumulate), summed smallest-magnitude-first; ``f32`` runs a single
    full-precision pass.  ``trans_y`` contracts y's LAST dim (q k^T).
    """
    contract = y.ndim - 1 if trans_y else 0
    dims = (((x.ndim - 1,), (contract,)), ((), ()))

    def one(a, b):
        return jax.lax.dot_general(a, b, dims,
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


# ------------------------------------------------------------ mask logic

def _keep_mask(cfg: FlashConfig, rows, cols, *, q_len: int, kv_len: int):
    """Boolean keep-mask for global (row, col) index grids."""
    keep = (cols < kv_len) & (rows < q_len)
    if cfg.causal:
        keep &= cols <= rows
        if cfg.window is not None:
            keep &= cols > rows - cfg.window
    return keep


def _block_live(cfg: FlashConfig, i, j, bq: int, bkv: int):
    """Whether KV block j intersects the mask of q block i at all.

    Causal: skip blocks fully above the diagonal.  Sliding window:
    additionally skip blocks fully left of every row's window.
    """
    live = jnp.bool_(True)
    if cfg.causal:
        live &= (j * bkv) <= ((i + 1) * bq - 1)
        if cfg.window is not None:
            live &= (j + 1) * bkv - 1 > i * bq - cfg.window
    return live


def _maybe_softcap(cfg: FlashConfig, s):
    if cfg.softcap is None:
        return s, None
    t = jnp.tanh(s / cfg.softcap)
    return cfg.softcap * t, t


# --------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, cfg: FlashConfig,
                q_len: int, kv_len: int, n_kv: int):
    i, j = pl.program_id(2), pl.program_id(3)
    bq = q_ref.shape[2]
    bkv = k_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_block_live(cfg, i, j, bq, bkv))
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (bkv, hd)
        s = _policy_dot(q, k, cfg.precision, trans_y=True)   # (bq, bkv)
        s, _ = _maybe_softcap(cfg, s)
        rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        cols = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        s = jnp.where(_keep_mask(cfg, rows, cols, q_len=q_len,
                                 kv_len=kv_len), s, NEG_INF)

        m_prev = m_ref[:, :1]                          # (bq, 1)
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                         # (bq, bkv) fp32
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)            # (bkv, hd)
        pv = _policy_dot(p, v, cfg.precision)          # (bq, hd)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_kv - 1)
    def _store():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[:, :1] +
                         jnp.log(jnp.maximum(l_ref[:, :1], 1e-30)))


def _fwd_impl(cfg: FlashConfig, qh, kh, vh, group: int,
              q_len: int, kv_len: int):
    """qh: (B, H, Sq_p, hd_p); kh/vh: (B, Kv, Skv_p, hd_p) — padded,
    head-major.  Returns (out (B,H,Sq_p,hd_p) fp32, lse (B,H,Sq_p,1)).

    The per-row vectors (``lse`` here, ``di`` in the backward) carry a
    trailing unit dim: a ``(bq, 1)`` block is one the TPU tiling accepts
    (sublane dim a multiple of 8, lane dim the whole array dim) at any
    head count, and it broadcasts against the ``(bq, bkv)`` score tile
    without a lane-to-sublane relayout."""
    b, h, sq_p, hd_p = qh.shape
    skv_p = kh.shape[2]
    bq = min(cfg.block_q, sq_p)
    bkv = min(cfg.block_kv, skv_p)
    n_q, n_kv = sq_p // bq, skv_p // bkv

    kernel = functools.partial(
        _fwd_kernel, cfg=cfg, q_len=q_len, kv_len=kv_len, n_kv=n_kv)
    return pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd_p), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, hd_p),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bkv, hd_p),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd_p), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq_p, hd_p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),     # m (lane-replicated)
            pltpu.VMEM((bq, 128), jnp.float32),     # l
            pltpu.VMEM((bq, hd_p), jnp.float32),    # unnormalized acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=cfg.interpret,
    )(qh, kh, vh)


# -------------------------------------------------------------- backward

def _recompute_p(cfg, q, k, lse, i, j, bq, bkv, q_len, kv_len):
    """Rebuild the (bq, bkv) probability tile and the softcap chain term."""
    s = _policy_dot(q, k, cfg.precision, trans_y=True)
    s_eff, t = _maybe_softcap(cfg, s)
    rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    cols = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    keep = _keep_mask(cfg, rows, cols, q_len=q_len, kv_len=kv_len)
    p = jnp.where(keep, jnp.exp(s_eff - lse), 0.0)
    return p, t, keep


def _chain_softcap(cfg, ds, t):
    """d(cap*tanh(s/cap))/ds = 1 - tanh^2."""
    return ds if t is None else ds * (1.0 - t * t)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
                   acc_ref, *, cfg: FlashConfig, q_len: int, kv_len: int,
                   n_kv: int):
    i, j = pl.program_id(2), pl.program_id(3)
    bq = q_ref.shape[2]
    bkv = k_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_block_live(cfg, i, j, bq, bkv))
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]                            # (bq, 1)
        di = di_ref[0, 0]
        p, t, _ = _recompute_p(cfg, q, k, lse, i, j, bq, bkv,
                               q_len, kv_len)
        dp = _policy_dot(do, v, cfg.precision, trans_y=True)  # (bq, bkv)
        ds = _chain_softcap(cfg, p * (dp - di), t)
        acc_ref[...] += _policy_dot(ds, k, cfg.precision)     # (bq, hd)

    @pl.when(j == n_kv - 1)
    def _store():
        dq_ref[0, 0] = acc_ref[...]


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, cfg: FlashConfig,
                    q_len: int, kv_len: int, n_q: int):
    j, i = pl.program_id(2), pl.program_id(3)      # kv outer, q inner
    bq = q_ref.shape[2]
    bkv = k_ref.shape[2]

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_block_live(cfg, i, j, bq, bkv))
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]                            # (bq, 1)
        di = di_ref[0, 0]
        p, t, _ = _recompute_p(cfg, q, k, lse, i, j, bq, bkv,
                               q_len, kv_len)
        # dv = p^T do ; dk = ds^T q — transpose via swapped operands.
        dv_acc[...] += _policy_dot(p.T, do, cfg.precision)    # (bkv, hd)
        dp = _policy_dot(do, v, cfg.precision, trans_y=True)
        ds = _chain_softcap(cfg, p * (dp - di), t)
        dk_acc[...] += _policy_dot(ds.T, q, cfg.precision)    # (bkv, hd)

    @pl.when(i == n_q - 1)
    def _store():
        dk_ref[0, 0] = dk_acc[...]
        dv_ref[0, 0] = dv_acc[...]


def _bwd_impl(cfg: FlashConfig, qh, kh, vh, out, lse, do, group: int,
              q_len: int, kv_len: int):
    """Head-major padded grads: (dqh, dkh_perhead, dvh_perhead) where the
    k/v grads are PER QUERY HEAD (B, H, Skv_p, hd_p) — the caller sums
    each GQA group down to the Kv heads."""
    b, h, sq_p, hd_p = qh.shape
    skv_p = kh.shape[2]
    bq = min(cfg.block_q, sq_p)
    bkv = min(cfg.block_kv, skv_p)
    n_q, n_kv = sq_p // bq, skv_p // bkv

    di = jnp.sum(out * do, axis=-1, keepdims=True)    # (B,H,Sq_p,1) fp32

    q_spec = pl.BlockSpec((1, 1, bq, hd_p), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bkv, hd_p),
                           lambda b, h, i, j, g=group: (b, h // g, j, 0))
    row_spec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, cfg=cfg, q_len=q_len,
                          kv_len=kv_len, n_kv=n_kv),
        grid=(b, h, n_q, n_kv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, hd_p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, hd_p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=cfg.interpret,
    )(qh, kh, vh, do, lse, di)

    # kv-major grid: q walk innermost, accumulators per kv tile.
    q_spec_t = pl.BlockSpec((1, 1, bq, hd_p), lambda b, h, j, i: (b, h, i, 0))
    kv_spec_t = pl.BlockSpec((1, 1, bkv, hd_p),
                             lambda b, h, j, i, g=group: (b, h // g, j, 0))
    row_spec_t = pl.BlockSpec((1, 1, bq, 1),
                              lambda b, h, j, i: (b, h, i, 0))
    dkv_out = pl.BlockSpec((1, 1, bkv, hd_p), lambda b, h, j, i: (b, h, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, cfg=cfg, q_len=q_len,
                          kv_len=kv_len, n_q=n_q),
        grid=(b, h, n_kv, n_q),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t,
                  row_spec_t],
        out_specs=[dkv_out, dkv_out],
        out_shape=[jax.ShapeDtypeStruct((b, h, skv_p, hd_p), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, skv_p, hd_p), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bkv, hd_p), jnp.float32),
                        pltpu.VMEM((bkv, hd_p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=cfg.interpret,
    )(qh, kh, vh, do, lse, di)
    return dq, dk, dv


# ----------------------------------------------------- layout + custom VJP

def _pad_seq_lengths(cfg: FlashConfig, sq: int, skv: int, hd: int):
    """(sq_p, skv_p, hd_p): block-multiple seq pads, 128-lane head pad."""
    bq = min(cfg.block_q, _round_up(sq, 8))
    bkv = min(cfg.block_kv, _round_up(skv, 128))
    return _round_up(sq, bq), _round_up(skv, bkv), _round_up(hd, 128)


def _q_to_heads(x, cfg: FlashConfig, skv: int):
    """(B,Sq,Kv,G,hd) -> padded head-major (B, Kv*G, Sq_p, hd_p).

    Zero padding: extra hd columns contribute 0 to scores and produce 0
    output columns; extra rows are masked / sliced."""
    bsz, sq, kvh, grp, hd = x.shape
    sq_p, _, hd_p = _pad_seq_lengths(cfg, sq, skv, hd)
    xh = x.reshape(bsz, sq, kvh * grp, hd).transpose(0, 2, 1, 3)
    return jnp.pad(xh, ((0, 0), (0, 0), (0, sq_p - sq), (0, hd_p - hd)))


def _kv_to_heads(x, cfg: FlashConfig, sq: int):
    """(B,Skv,Kv,hd) -> padded head-major (B, Kv, Skv_p, hd_p)."""
    skv, hd = x.shape[1], x.shape[3]
    _, skv_p, hd_p = _pad_seq_lengths(cfg, sq, skv, hd)
    xh = x.transpose(0, 2, 1, 3)
    return jnp.pad(xh, ((0, 0), (0, 0), (0, skv_p - skv), (0, hd_p - hd)))


def _to_heads(q, k, v, cfg: FlashConfig):
    """Model layout -> padded head-major kernel layout (all three)."""
    sq, skv = q.shape[1], k.shape[1]
    return (_q_to_heads(q, cfg, skv), _kv_to_heads(k, cfg, sq),
            _kv_to_heads(v, cfg, sq))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg: FlashConfig, q, k, v):
    return _flash_fwd(cfg, q, k, v)[0]


def _flash_fwd(cfg: FlashConfig, q, k, v):
    bsz, sq, kvh, grp, hd = q.shape
    qh, kh, vh = _to_heads(q, k, v, cfg)
    out_h, lse = _fwd_impl(cfg, qh, kh, vh, grp, sq, k.shape[1])
    out = (out_h[:, :, :sq, :hd]
           .transpose(0, 2, 1, 3)
           .reshape(bsz, sq, kvh, grp, hd))
    return out, (q, k, v, out_h, lse)


def _flash_bwd(cfg: FlashConfig, res, g):
    q, k, v, out_h, lse = res
    bsz, sq, kvh, grp, hd = q.shape
    skv = k.shape[1]
    qh, kh, vh = _to_heads(q, k, v, cfg)
    doh = _q_to_heads(g.astype(jnp.float32), cfg, skv)
    dqh, dkh, dvh = _bwd_impl(cfg, qh, kh, vh, out_h, lse, doh, grp,
                              sq, skv)
    dq = (dqh[:, :, :sq, :hd].transpose(0, 2, 1, 3)
          .reshape(bsz, sq, kvh, grp, hd))
    # per-q-head kv grads: sum each GQA group down to its kv head
    def fold(dxh):
        dx = dxh[:, :, :skv, :hd].reshape(bsz, kvh, grp, skv, hd).sum(2)
        return dx.transpose(0, 2, 1, 3)               # (B, Skv, Kv, hd)
    return (dq.astype(q.dtype), fold(dkh).astype(k.dtype),
            fold(dvh).astype(v.dtype))


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None,
                    softcap: float | None = None,
                    precision: str = "bf16",
                    block_q: int = 128, block_kv: int = 128,
                    interpret: bool = False) -> jax.Array:
    """Fused flash attention in the model's GQA layout.

    q: (B, Sq, Kv, G, hd) PRE-SCALED queries (the model applies
    head_dim**-0.5 before the call, as for the reference path);
    k/v: (B, Skv, Kv, hd).  Returns (B, Sq, Kv, G, hd) fp32.
    Differentiable via the fused Pallas backward kernels.
    """
    cfg = FlashConfig(causal=causal, window=window, softcap=softcap,
                      precision=precision, block_q=block_q,
                      block_kv=block_kv, interpret=interpret)
    return _flash(cfg, q, k, v)


# ---------------------------------------------------------------- decode

def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, cfg: FlashConfig,
                   s_cache: int, n_kv: int):
    b, j = pl.program_id(0), pl.program_id(2)
    bkv = k_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)               # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)               # (bkv, hd)
    s = _policy_dot(q, k, cfg.precision, trans_y=True)  # (1, bkv)
    s, _ = _maybe_softcap(cfg, s)

    pos = pos_ref[b]
    cols = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
    if cfg.window is not None:
        # Ring buffer: slot c holds absolute position
        # pos - ((pos - c) mod s_cache); negative => never written.
        abs_pos = pos - ((pos - cols) % s_cache)
        keep = (abs_pos >= 0) & (cols < s_cache)
    else:
        keep = (cols <= pos) & (cols < s_cache)
    s = jnp.where(keep, s, NEG_INF)

    m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    v = v_ref[0, 0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + _policy_dot(p, v, cfg.precision)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_kv - 1)
    def _store():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def flash_decode(q, k_cache, v_cache, pos, *, window: int | None = None,
                 softcap: float | None = None, precision: str = "bf16",
                 block_kv: int = 128, interpret: bool = False) -> jax.Array:
    """Single-token fused decode against the full-capacity KV cache.

    q: (B, 1, Kv, G, hd) pre-scaled; k_cache/v_cache: (B, S_cache, Kv,
    hd) AFTER the current token's row was written; pos: (B,) int32
    per-row absolute positions (continuous batching: every slot decodes
    at its own position).  ``window`` selects the ring-buffer mask
    (slot = pos mod S_cache) vs the linear ``col <= pos`` mask.
    Returns (B, 1, Kv, G, hd) fp32.
    """
    bsz, sq, kvh, grp, hd = q.shape
    assert sq == 1, "flash_decode is the single-token cell"
    s_cache = k_cache.shape[1]
    cfg = FlashConfig(causal=False, window=window, softcap=softcap,
                      precision=precision, block_kv=block_kv,
                      interpret=interpret)
    hd_p = _round_up(hd, 128)
    bkv = min(block_kv, _round_up(s_cache, 128))
    skv_p = _round_up(s_cache, bkv)
    h = kvh * grp

    qh = q.reshape(bsz, 1, h, hd).transpose(0, 2, 1, 3)    # (B,H,1,hd)
    qh = jnp.pad(qh, ((0, 0), (0, 0), (0, 0), (0, hd_p - hd)))
    kh = jnp.pad(k_cache.transpose(0, 2, 1, 3),
                 ((0, 0), (0, 0), (0, skv_p - s_cache), (0, hd_p - hd)))
    vh = jnp.pad(v_cache.transpose(0, 2, 1, 3),
                 ((0, 0), (0, 0), (0, skv_p - s_cache), (0, hd_p - hd)))

    kernel = functools.partial(_decode_kernel, cfg=cfg, s_cache=s_cache,
                               n_kv=skv_p // bkv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, h, skv_p // bkv),
        in_specs=[
            pl.BlockSpec((1, 1, 1, hd_p), lambda b, h, j, *_: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bkv, hd_p),
                         lambda b, h, j, *_, g=grp: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bkv, hd_p),
                         lambda b, h, j, *_, g=grp: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd_p),
                               lambda b, h, j, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 128), jnp.float32),
            pltpu.VMEM((1, 128), jnp.float32),
            pltpu.VMEM((1, hd_p), jnp.float32),
        ],
    )
    out_h = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, h, 1, hd_p), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pos.astype(jnp.int32), qh, kh, vh)
    return (out_h[:, :, :, :hd].transpose(0, 2, 1, 3)
            .reshape(bsz, 1, kvh, grp, hd))
