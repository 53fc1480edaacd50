"""Multi-head latent attention (MLA, DeepSeek-V2, arXiv:2405.04434).

Each token's keys and values come from one compressed latent: the
token is projected to ``c`` (``kv_lora_rank`` wide, then RMSNorm) and
to one rope key ``k_pe`` (``qk_rope_dim`` wide) that all heads share.
``wkv_b`` lifts ``c`` to every head's no-rope key (``head_dim``) and
value (``v_head_dim``).  Queries have a no-rope and a rope part per
head.  Rope rotates dimension pairs ``(2i, 2i + 1)`` as DeepSeek does,
with YaRN frequencies where the configuration has them.

The cache holds only ``c`` and ``k_pe`` (``LatentCache``): 512 + 64
values per token and layer at DeepSeek-V2-Lite's widths, against 16
heads x 256 for an expanded K and V.  They are two leaves, so that
each keeps a layout whose minor dim is its own row (a 576-wide leaf
would be stored sequence-minor on a TPU, and every row write would
relayout it).  Two paths read the cache:

* train and prefill **expand** the latent to per-head K and V and run
  the attention family's chunked flash-over-KV forward;
* decode is **absorbed**: the no-rope key half of ``wkv_b`` (``W_UK``)
  is folded into the query, so each head scores ``c`` directly (plus
  its rope query against ``k_pe``), and the value half (``W_UV``) is
  applied to the attention-weighted ``c`` afterwards.  No per-head K
  or V is built.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, Yarn
from repro.core import ops
from repro.core.ops import Route
from repro.core.refined_matmul import peinsum
from repro.models import layers as L

__all__ = ["LatentCache", "init_mla", "mla_attention", "softmax_scale"]


class LatentCache(NamedTuple):
    latent: jax.Array  # (B, S, kv_lora_rank): c after its RMSNorm
    k_rope: jax.Array  # (B, S, qk_rope_dim): the shared key, rotated


def init_mla(key, cfg: ModelConfig, *, stack: tuple[int, ...] = ()) -> dict:
    h, nope, rope = cfg.num_heads, cfg.head_dim, cfg.qk_rope_dim
    rank, vd = cfg.kv_lora_rank, cfg.v_head_dim
    kq, ka, kb, ko = jax.random.split(key, 4)
    return {
        "wq": L.init_linear(kq, cfg.d_model, h * (nope + rope), stack=stack),
        "wkv_a": L.init_linear(ka, cfg.d_model, rank + rope, stack=stack),
        "kv_norm": L.init_rmsnorm(rank, stack=stack),
        "wkv_b": L.init_linear(kb, rank, h * (nope + vd), stack=stack),
        "wo": L.init_linear(ko, h * vd, cfg.d_model, stack=stack,
                            scale=(h * vd) ** -0.5),
    }


# ------------------------------------------------------------------ rope

def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope_frequencies(dim: int, theta: float, yarn: Yarn | None):
    """Inverse frequencies of the ``dim // 2`` rotated pairs, YaRN-
    interpolated where ``yarn`` is given (DeepSeek's ``yarn_find_
    correction_range`` and linear ramp)."""
    base = theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if yarn is None:
        return 1.0 / base

    def correction(rotations):
        return (dim * math.log(yarn.original_max_pos
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(yarn.beta_fast)), 0)
    high = min(math.ceil(correction(yarn.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    extrapolated = 1.0 - ramp
    return (1.0 / (yarn.factor * base) * ramp
            + 1.0 / base * extrapolated)


def _rope_tables(positions, cfg: ModelConfig):
    """sin/cos (..., qk_rope_dim / 2) in f32 at ``positions``."""
    freq = _rope_frequencies(cfg.qk_rope_dim, cfg.rope_theta, cfg.yarn)
    ang = positions.astype(jnp.float32)[..., None] * freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    y = cfg.yarn
    if y is not None and y.mscale_all_dim:
        m = (_yarn_mscale(y.factor, y.mscale)
             / _yarn_mscale(y.factor, y.mscale_all_dim))
        if m != 1.0:
            sin, cos = sin * m, cos * m
    return sin, cos


def _rope_pairs(x, sin, cos):
    """Rotate pairs (2i, 2i+1) of x (..., r) by sin/cos (..., r/2)."""
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def softmax_scale(cfg: ModelConfig) -> float:
    """``(head_dim + qk_rope_dim)**-0.5``, times ``mscale**2`` under
    YaRN as in DeepSeek's modelling code."""
    scale = (cfg.head_dim + cfg.qk_rope_dim) ** -0.5
    y = cfg.yarn
    if y is not None and y.mscale_all_dim:
        scale *= _yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


# ------------------------------------------------------------- attention

def _project(p, x, positions, cfg: ModelConfig, policy):
    """x (B,S,D) -> q_nope (B,S,H,nope), q_pe (B,S,H,rope) rotated, and
    the cache rows: c (B,S,rank) normed, k_pe (B,S,rope) rotated; all
    in x.dtype.  ``positions`` is (S,) or (B,S)."""
    b, s, _ = x.shape
    h, nope, rope = cfg.num_heads, cfg.head_dim, cfg.qk_rope_dim
    dtype = x.dtype
    q = L.linear(p["wq"], x, policy).reshape(b, s, h, nope + rope)
    ckv = L.linear(p["wkv_a"], x, policy)
    c = L.rmsnorm(p["kv_norm"], ckv[..., :cfg.kv_lora_rank], cfg.norm_eps)
    sin, cos = _rope_tables(positions, cfg)
    if sin.ndim == 2:                    # (S, r/2): same for every row
        sin, cos = sin[None], cos[None]
    q_pe = _rope_pairs(q[..., nope:], sin[:, :, None], cos[:, :, None])
    k_pe = _rope_pairs(ckv[..., cfg.kv_lora_rank:], sin, cos)
    return (q[..., :nope].astype(dtype), q_pe.astype(dtype),
            LatentCache(latent=c.astype(dtype), k_rope=k_pe.astype(dtype)))


def _expanded(p, q_nope, q_pe, rows: LatentCache, cfg: ModelConfig, policy,
              kv_chunk: int):
    """Causal attention with per-head K/V lifted from the latent."""
    b, s, h, nope = q_nope.shape
    dtype = q_nope.dtype
    kv = L.linear(p["wkv_b"], rows.latent, policy).reshape(
        b, s, h, nope + cfg.v_head_dim).astype(dtype)
    k_pe = jnp.broadcast_to(rows.k_rope[:, :, None],
                            (b, s, h, cfg.qk_rope_dim))
    k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    q = jnp.concatenate([q_nope, q_pe], -1) * softmax_scale(cfg)
    out = ops.attention_forward(
        q.astype(dtype)[:, :, :, None], k, kv[..., nope:], causal=True,
        window=None, softcap=None, policy=policy, kv_chunk=kv_chunk)
    return out.reshape(b, s, h * cfg.v_head_dim)


def _absorbed(p, q_nope, q_pe, cache: LatentCache, pos, cfg: ModelConfig,
              policy):
    """One token per row against the latent cache (post-write): the
    heads share ``c`` and ``k_pe`` as one multi-query attention."""
    b, _, h, nope = q_nope.shape
    rank, dtype = cfg.kv_lora_rank, q_nope.dtype
    c = cache.latent.astype(dtype)                              # B,S,R
    w = p["wkv_b"]["w"].reshape(rank, h, nope + cfg.v_head_dim)
    q_lat = peinsum("bhn,rhn->bhr", q_nope[:, 0], w[..., :nope], policy)
    sc = (peinsum("bhr,bsr->bhs", q_lat.astype(dtype), c, policy)
          + peinsum("bhp,bsp->bhs", q_pe[:, 0],
                    cache.k_rope.astype(dtype), policy))
    keep = jnp.arange(c.shape[1])[None, None, :] <= pos[:, None, None]
    sc = jnp.where(keep, sc * softmax_scale(cfg), -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o_lat = peinsum("bhs,bsr->bhr", pr.astype(dtype), c, policy)
    out = peinsum("bhr,rhv->bhv", o_lat.astype(dtype), w[..., nope:], policy)
    return out.reshape(b, 1, h * cfg.v_head_dim)


def mla_attention(p: dict, x: jax.Array, *, cfg: ModelConfig, mode: str,
                  policy: str | Route, cache: LatentCache | None = None,
                  pos: jax.Array | None = None, layer=None,
                  kv_chunk: int = 2048,
                  ) -> tuple[jax.Array, LatentCache | None]:
    """Returns (output (B,S,D) in x.dtype, the prefill's latent cache,
    the decode's updated cache, or None in training).  In decode,
    ``cache`` holds every layer's cache stacked on a leading axis: the
    rows are written at ``layer``, that layer's slice is read, and the
    stack comes back."""
    b, s, _ = x.shape
    dtype = x.dtype
    if mode in ("train", "prefill"):
        q_nope, q_pe, rows = _project(p, x, jnp.arange(s), cfg, policy)
        out = _expanded(p, q_nope, q_pe, rows, cfg, policy, kv_chunk)
        new_cache = rows if mode == "prefill" else None
    elif mode == "decode":
        assert cache is not None and pos is not None and layer is not None
        assert s == 1
        pos = jnp.broadcast_to(pos, (b,))
        q_nope, q_pe, rows = _project(p, x, pos[:, None], cfg, policy)
        new_cache = jax.tree.map(
            lambda full, row: full.at[layer, jnp.arange(b), pos].set(
                row[:, 0].astype(full.dtype)), cache, rows)
        view = jax.tree.map(
            lambda full: jax.lax.dynamic_index_in_dim(full, layer,
                                                      keepdims=False),
            new_cache)
        out = _absorbed(p, q_nope, q_pe, view, pos, cfg, policy)
    else:
        raise ValueError(f"unknown mode {mode!r}")
    return L.linear(p["wo"], out.astype(dtype), policy).astype(dtype), \
        new_cache
