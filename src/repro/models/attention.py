"""GQA attention with RoPE, sliding windows, KV caches and
backend-routed fused evaluation.

The score/softmax/value pipeline dispatches through the ATTENTION
kernel family of the op registry (``repro.core.ops``): the ``xla``
reference impl is the chunked two-GEMM path implemented here
(``reference_forward`` / ``reference_decode`` — score and value
contractions via ``peinsum``, online softmax in jnp between them),
while ``pallas_fused`` runs the flash-attention Pallas kernels
(``kernels.attention_fused``) whose score tile never leaves VMEM.
Either way the contractions honor the precision-policy ladder
(``policy`` argument = policy string or ``core.ops.Route``), so the
paper's refinement ladder applies to the attention GEMMs exactly as to
the projections.

Sliding-window ("local") layers keep a RING-BUFFER cache of `window`
entries: slot ``t % window`` holds token ``t`` (RoPE applied at write
time with absolute positions). This is what makes `long_500k` decode
cheap for gemma3 (5:6 of layers) and mixtral (all layers): the cache
never exceeds the window.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import ops
from repro.core.ops import Route
from repro.core.ops import paged as paged_kv
from repro.core.ops.paged import PagedKVCache
from repro.core.refined_matmul import peinsum
from repro.models import layers as L

__all__ = ["init_attn", "attention", "AttnCache", "rope_table",
           "reference_forward", "reference_decode",
           "reference_paged_decode"]

NEG_INF = -1e30


class AttnCache(NamedTuple):
    k: jax.Array  # (B, S_cache, Kv, hd)
    v: jax.Array  # (B, S_cache, Kv, hd)


# ------------------------------------------------------------------ rope

def rope_table(positions: jax.Array, head_dim: int, theta: float,
               dtype=jnp.float32) -> tuple[jax.Array, jax.Array]:
    """sin/cos tables for GPT-NeoX-style rotate-half RoPE.

    positions: (...,) int32 -> (..., head_dim/2) each.
    """
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(ang).astype(dtype), jnp.cos(ang).astype(dtype)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (B, S, H, hd); sin/cos: (S, hd/2) or (B, S, hd/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if sin.ndim == 2:  # (S, half) -> broadcast over batch and heads
        sin_, cos_ = sin[None, :, None, :], cos[None, :, None, :]
    else:              # (B, S, half)
        sin_, cos_ = sin[:, :, None, :], cos[:, :, None, :]
    return jnp.concatenate(
        [x1 * cos_ - x2 * sin_, x2 * cos_ + x1 * sin_], axis=-1
    ).astype(x.dtype)


# ------------------------------------------------------------------ init

def init_attn(key, d_model: int, num_heads: int, num_kv_heads: int,
              head_dim: int, *, bias: bool = False,
              stack: tuple[int, ...] = ()) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": L.init_linear(kq, d_model, num_heads * head_dim, bias=bias, stack=stack),
        "wk": L.init_linear(kk, d_model, num_kv_heads * head_dim, bias=bias, stack=stack),
        "wv": L.init_linear(kv, d_model, num_kv_heads * head_dim, bias=bias, stack=stack),
        "wo": L.init_linear(ko, num_heads * head_dim, d_model, bias=bias,
                            scale=(num_heads * head_dim) ** -0.5, stack=stack),
    }


# ------------------------------------------------- grouped score helpers

def _scores(q, k, policy, softcap):
    """q: (B,Q,Kv,G,hd) x k: (B,S,Kv,hd) -> (B,Kv,G,Q,S) fp32."""
    s = peinsum("bqkgd,bskd->bkgqs", q, k, policy)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    return s


def _values(p, v, policy):
    """p: (B,Kv,G,Q,S) x v: (B,S,Kv,hd) -> (B,Q,Kv,G,hd) fp32."""
    return peinsum("bkgqs,bskd->bqkgd", p, v, policy)


def _flash_over_kv(q, k, v, mask_fn, policy, softcap, kv_chunk: int):
    """Online-softmax attention, scanning KV chunks (flash-style).

    q: (B,Q,Kv,G,hd); k: (B,S,Kv,hd); v: (B,S,Kv,vd). mask_fn(q_idx,
    k_idx) -> bool keep-mask broadcastable to (Q, chunk). Returns
    (B,Q,Kv,G,vd) fp32.
    """
    b, qlen, kvh, grp, _ = q.shape
    vd = v.shape[-1]
    s = k.shape[1]
    if s % kv_chunk:  # pad keys to a chunk multiple; mask the tail
        pad = kv_chunk - s % kv_chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        inner = mask_fn
        mask_fn = lambda qi, ki: inner(qi, ki) & (ki < s)
    n_chunks = k.shape[1] // kv_chunk
    q_idx = jnp.arange(qlen)

    def step(carry, chunk_i):
        m, l, acc = carry
        start = chunk_i * kv_chunk
        kc = jax.lax.dynamic_slice_in_dim(k, start, kv_chunk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, start, kv_chunk, axis=1)
        sc = _scores(q, kc, policy, softcap)            # (B,Kv,G,Q,c)
        keep = mask_fn(q_idx[:, None], start + jnp.arange(kv_chunk)[None, :])
        sc = jnp.where(keep[None, None, None], sc, NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        scale = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new[..., None])
        l_new = l * scale + p.sum(axis=-1)
        acc_new = acc * scale.transpose(0, 3, 1, 2)[..., None] + _values(
            p.astype(q.dtype), vc, policy)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, grp, qlen), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, grp, qlen), jnp.float32)
    acc0 = jnp.zeros((b, qlen, kvh, grp, vd), jnp.float32)
    # Nested remat: without it the backward loads STACKED per-chunk f32
    # score/prob tensors (B,Kv,G,Q,c) x n_chunks from HBM — the dominant
    # memory term of every train/prefill cell at baseline. Recomputing
    # them from (q, k-chunk) costs ~2x the score flops, which are >20x
    # cheaper than the byte traffic they replace (§Perf iteration A2).
    step = jax.checkpoint(step)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), jnp.arange(n_chunks))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return out


# ------------------------------------------- reference (xla) backend

def reference_forward(q, k, v, *, causal: bool, window: int | None,
                      softcap: float | None, policy, kv_chunk: int = 2048):
    """The chunked two-GEMM attention path — the registry's ``xla``
    attention backend and the fused kernels' parity oracle.

    q: (B,Sq,Kv,G,hd) pre-scaled; k/v: (B,Skv,Kv,hd). fp32 out.
    """
    if not causal:
        window = None
    if causal and window is not None:
        mask_fn = lambda qi, ki: (ki <= qi) & (ki > qi - window)
    elif causal:
        mask_fn = lambda qi, ki: ki <= qi
    else:
        mask_fn = lambda qi, ki: (ki >= 0) & (qi >= -1)
    return _flash_over_kv(q, k, v, mask_fn, policy, softcap,
                          kv_chunk=min(kv_chunk, k.shape[1]))


def reference_decode(q, k_cache, v_cache, pos, *, window: int | None,
                     softcap: float | None, policy):
    """Single-token decode against the post-write cache at per-row
    positions (ring-buffer mask when ``window`` is set)."""
    s_cache = k_cache.shape[1]
    jdx = jnp.arange(s_cache)[None, :]               # (1, S)
    if window is not None:
        # Absolute position held in slot j after row i wrote pos[i].
        abs_pos = pos[:, None] - ((pos[:, None] - jdx) % s_cache)
        keep = abs_pos >= 0                          # (B, S)
    else:
        keep = jdx <= pos[:, None]                   # (B, S)
    sc = _scores(q, k_cache, policy, softcap)        # (B,Kv,G,1,S)
    sc = jnp.where(keep[:, None, None, None], sc, NEG_INF)
    pr = jax.nn.softmax(sc, axis=-1)
    return _values(pr.astype(q.dtype), v_cache, policy)


def reference_paged_decode(q, cache: PagedKVCache, pos, *,
                           window: int | None, softcap: float | None,
                           policy):
    """Paged decode = page-table gather + the UNCHANGED dense decode.

    Gathering the pool through the table reproduces the dense per-slot
    layout row for row (trash-page rows land where never-written dense
    rows sit and are masked identically), so an unquantized paged
    decode is bitwise the dense decode; quantized pools additionally
    dequantize by the stored per-row/head scales."""
    k, v = paged_kv.gather_dense(cache)          # (B, s_cache, Kv, hd)
    return reference_decode(q, k.astype(q.dtype), v.astype(q.dtype), pos,
                            window=window, softcap=softcap, policy=policy)


# ------------------------------------------------------------- attention

def attention(
    p: dict,
    x: jax.Array,
    *,
    mode: str,                       # "train" | "prefill" | "decode"
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    policy: str | Route,
    rope_theta: float | None = 10_000.0,   # None -> no RoPE (whisper)
    window: int | None = None,             # sliding window (local layers)
    softcap: float | None = None,
    causal: bool = True,                   # False for encoder self-attn
    cache: AttnCache | None = None,
    pos: jax.Array | None = None,          # decode: (B,) int32 positions
    cross_kv: AttnCache | None = None,     # cross-attention: attend here
    kv_chunk: int = 2048,  # §Perf A6: fewer online-softmax acc round trips
) -> tuple[jax.Array, AttnCache | None]:
    """Returns (output (B,S,D) in x.dtype, new/updated cache or None)."""
    b, s, d = x.shape
    grp = num_heads // num_kv_heads
    dtype = x.dtype

    q = L.linear(p["wq"], x, policy).reshape(b, s, num_kv_heads, grp, head_dim)
    if cross_kv is None:
        k = L.linear(p["wk"], x, policy).reshape(b, s, num_kv_heads, head_dim)
        v = L.linear(p["wv"], x, policy).reshape(b, s, num_kv_heads, head_dim)
    else:
        k = v = None  # keys/values come from the encoder cache

    scale = head_dim ** -0.5
    q = (q * scale).astype(dtype)

    new_cache: AttnCache | None = None

    if cross_kv is not None:
        # Cross-attention: no RoPE, no causal mask, static cache.
        kc, vc = cross_kv.k.astype(dtype), cross_kv.v.astype(dtype)
        out = ops.attention_forward(
            q, kc, vc, causal=False, window=None, softcap=softcap,
            policy=policy, kv_chunk=kv_chunk)
    elif mode in ("train", "prefill", "encode"):
        positions = jnp.arange(s)
        if rope_theta is not None:
            sin, cos = rope_table(positions, head_dim, rope_theta, dtype)
            q = apply_rope(
                q.reshape(b, s, num_heads, head_dim), sin, cos
            ).reshape(b, s, num_kv_heads, grp, head_dim)
            k = apply_rope(k.astype(dtype), sin, cos)
        k, v = k.astype(dtype), v.astype(dtype)

        out = ops.attention_forward(
            q, k, v, causal=causal, window=window, softcap=softcap,
            policy=policy, kv_chunk=kv_chunk)

        if mode == "prefill":
            if window is not None and s > window:
                # Ring buffer holding the last `window` tokens:
                # slot j <- token (s-1) - ((s-1-j) mod window)
                j = jnp.arange(window)
                tok = (s - 1) - ((s - 1 - j) % window)
                new_cache = AttnCache(k=k[:, tok], v=v[:, tok])
            else:
                new_cache = AttnCache(k=k, v=v)
    elif mode == "decode":
        assert cache is not None and pos is not None and s == 1
        # pos is a PER-ROW position vector (B,): slots in a continuous-
        # batching engine are admitted at different ticks, so every row
        # rotates, writes and masks at its own absolute position.
        pos = jnp.broadcast_to(pos, (b,))
        is_paged = isinstance(cache, PagedKVCache)
        s_cache = cache.s_cache if is_paged else cache.k.shape[1]
        if rope_theta is not None:
            sin, cos = rope_table(pos[:, None], head_dim, rope_theta,
                                  dtype)                 # (B,1,hd/2)
            q = apply_rope(
                q.reshape(b, 1, num_heads, head_dim), sin, cos
            ).reshape(b, 1, num_kv_heads, grp, head_dim)
            k = apply_rope(k.astype(dtype), sin, cos)
        k, v = k.astype(dtype), v.astype(dtype)

        slot = pos % s_cache if window is not None else pos       # (B,)
        if is_paged:
            # Same logical row as the dense write, stored through the
            # page table (inactive rows land on the trash page).
            new_cache = paged_kv.write_kv(cache, k[:, 0], v[:, 0], slot)
            out = ops.attention_paged_decode(
                q, new_cache, pos, window=window, softcap=softcap,
                policy=policy)
        else:
            row = jnp.arange(b)
            ck = cache.k.at[row, slot].set(k[:, 0].astype(cache.k.dtype))
            cv = cache.v.at[row, slot].set(v[:, 0].astype(cache.v.dtype))
            new_cache = AttnCache(k=ck, v=cv)

            out = ops.attention_decode(
                q, ck.astype(dtype), cv.astype(dtype), pos, window=window,
                softcap=softcap, policy=policy)
    else:
        raise ValueError(f"unknown mode {mode!r}")

    out = out.astype(dtype).reshape(b, s, num_heads * head_dim)
    return L.linear(p["wo"], out, policy).astype(dtype), new_cache
