"""Fused paged-KV decode: scalar-prefetched page-table indirection.

``flash_decode`` streams the dense per-slot cache ``(B, S_cache, Kv,
hd)``; this kernel streams a shared PAGE POOL ``(P, page_size, Kv, hd)``
through a per-slot page table instead.  The page table rides the
scalar-prefetch channel next to the position vector, so the KV
BlockSpec index map resolves the PHYSICAL page for grid cell
``(b, h, j)`` as ``table[b, j]`` before the DMA is issued — the kernel
body never sees the indirection, only a (page_size, hd) KV tile.

Logical rows keep the dense cache's meaning (row ``pos`` linear, row
``pos % s_cache`` ring), so the masks are copied verbatim from
``_decode_kernel``: logical column ``c = j * page_size + offset`` is
kept by exactly the predicate the dense kernel applies to cache slot
``c``.  Unallocated / freed table entries point at the reserved trash
page (0); their columns are always masked (they sit past ``pos`` or
outside the ring), so trash content never reaches the softmax.

Quantized pools (int8 payload + per-(row, kv-head) fp32 scales) are
dequantized in-kernel: the scale planes ride two more page-indirected
block streams and multiply the tile right after load, before the
policy-decomposed MXU dots.  The scales are laid out ``(P, Kv, ps, 1)``
so one (page, kv-head) scale tile is a ``(ps, 1)`` column: its last two
block dims equal the array's, which the TPU tiling accepts at any
``Kv`` and page size, and it broadcasts over the ``(ps, hd)`` payload
tile as loaded.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ops.paged import PagedKVCache
from repro.kernels.attention_fused import NEG_INF, _policy_dot, _round_up

__all__ = ["flash_paged_decode"]


def _paged_kernel(pos_ref, table_ref, q_ref, k_ref, v_ref, *rest,
                  precision: str, softcap: float | None,
                  window: int | None, s_cache: int, n_log: int,
                  page_size: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b, j = pl.program_id(0), pl.program_id(2)
    ps = page_size

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)               # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)               # (ps, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    if quantized:
        k = k * ks_ref[0, 0]                          # (ps, 1) scales
        v = v * vs_ref[0, 0]
    s = _policy_dot(q, k, precision, trans_y=True)    # (1, ps)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    pos = pos_ref[b]
    cols = j * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
    if window is not None:
        # Ring buffer: logical slot c holds absolute position
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
    acc_ref[...] = acc_ref[...] * alpha + _policy_dot(p, v, precision)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == n_log - 1)
    def _store():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def flash_paged_decode(q, cache: PagedKVCache, pos, *,
                       window: int | None = None,
                       softcap: float | None = None,
                       precision: str = "bf16",
                       interpret: bool = False) -> jax.Array:
    """Single-token fused decode against a post-write paged KV cache.

    q: (B, 1, Kv, G, hd) pre-scaled; ``cache`` a ``PagedKVCache`` whose
    current token's row was already written (``paged.write_kv``); pos:
    (B,) int32 per-row absolute positions.  ``window`` selects the
    ring-buffer mask (slot = pos mod s_cache) vs the linear mask, with
    ``s_cache = cache.s_cache``.  Returns (B, 1, Kv, G, hd) fp32 —
    token-exact vs ``flash_decode`` on the dense cache for unquantized
    pools.
    """
    bsz, sq, kvh, grp, hd = q.shape
    assert sq == 1, "flash_paged_decode is the single-token cell"
    ps = cache.page_size
    n_log = cache.page_table.shape[1]
    hd_p = _round_up(hd, 128)
    h = kvh * grp

    qh = q.reshape(bsz, 1, h, hd).transpose(0, 2, 1, 3)    # (B,H,1,hd)
    qh = jnp.pad(qh, ((0, 0), (0, 0), (0, 0), (0, hd_p - hd)))
    # Head-major pages: (P, ps, Kv, hd) -> (P, Kv, ps, hd_p) so one
    # BlockSpec slice is one (page, kv-head) tile.
    pad = ((0, 0), (0, 0), (0, 0), (0, hd_p - hd))
    kh = jnp.pad(cache.k_pages.transpose(0, 2, 1, 3), pad)
    vh = jnp.pad(cache.v_pages.transpose(0, 2, 1, 3), pad)

    kernel = functools.partial(
        _paged_kernel, precision=precision, softcap=softcap,
        window=window, s_cache=cache.s_cache, n_log=n_log,
        page_size=ps, quantized=cache.quantized)

    page_spec = pl.BlockSpec(
        (1, 1, ps, hd_p),
        lambda b, h, j, pos_ref, table_ref, g=grp:
            (table_ref[b, j], h // g, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, 1, hd_p), lambda b, h, j, *_: (b, h, 0, 0)),
        page_spec,
        page_spec,
    ]
    operands = [qh, kh, vh]
    if cache.quantized:
        scale_spec = pl.BlockSpec(
            (1, 1, ps, 1),
            lambda b, h, j, pos_ref, table_ref, g=grp:
                (table_ref[b, j], h // g, 0, 0))
        in_specs += [scale_spec, scale_spec]
        # (P, ps, Kv) -> (P, Kv, ps, 1): one (page, kv-head) column
        operands += [cache.k_scale.transpose(0, 2, 1)[..., None],
                     cache.v_scale.transpose(0, 2, 1)[..., None]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, h, n_log),
        in_specs=in_specs,
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
    )(pos.astype(jnp.int32), cache.page_table.astype(jnp.int32),
      *operands)
    return (out_h[:, :, :, :hd].transpose(0, 2, 1, 3)
            .reshape(bsz, 1, kvh, grp, hd))
