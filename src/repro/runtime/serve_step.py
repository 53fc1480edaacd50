"""Serve-step builders: prefill and single-token decode with padded,
shardable caches.

``prefill`` ingests the context and emits a cache PADDED to the decode
capacity (attention caches grow in place afterwards; ring-buffer local
caches are already window-sized; recurrent states are O(1)). ``decode``
is the cell lowered for the ``decode_32k`` / ``long_500k`` dry-runs —
one new token against the full-capacity cache, NOT a train step.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.ops import ExecutionPolicy
from repro.core.ops import paged as paged_kv
from repro.core.ops.paged import PagedKVCache
from repro.core.precision import PrecisionPolicy
from repro.models import api
from repro.models.attention import AttnCache
from repro.models.mla import LatentCache

__all__ = ["make_prefill", "make_decode", "make_engine_tick",
           "make_slot_splice", "pad_cache", "abstract_cache",
           "abstract_params", "attn_cache_walk", "paged_classes",
           "init_paged_cache"]

# Either policy flavour routes every model matmul below (ExecutionPolicy
# — or its legacy MatmulPolicy subclass — additionally selects the
# registered impl each op family's contractions run on via its
# ``backends`` mapping: ``{"attention": "pallas_fused"}`` runs prefill
# and per-slot decode on the fused flash-attention kernels, reading the
# ring/linear KV cache at the engine's per-row position vector
# in-kernel — decode demands the impl's ``decode`` capability at
# route-build time — and ``{"grouped": "pallas_grouped"}`` replaces the
# capacity-padded (E, C, D) MoE gather with sort-based dropless grouped
# GEMMs, keeping each slot's decode independent of which other requests
# share the batch).
Policy = PrecisionPolicy | ExecutionPolicy


def _attn_capacity(kind: str, cfg: ModelConfig, s_ctx: int) -> int | None:
    if kind in ("attn", "shared_attn"):
        return s_ctx
    if kind == "attn_local":
        return s_ctx if cfg.window is None else min(s_ctx, cfg.window)
    return None  # cross_attn (fixed enc length) and stateless/recurrent


def pad_cache(cache: dict, cfg: ModelConfig, s_ctx: int) -> dict:
    """Pad every growable attention cache to its decode capacity."""
    out: dict[str, Any] = {}
    for i, seg in enumerate(cfg.segments):
        seg_c = cache[f"seg{i}"]
        new_seg: dict[str, Any] = {}
        for j, kind in enumerate(seg.pattern):
            c = seg_c[f"pos{j}"]
            cap = _attn_capacity(kind, cfg, s_ctx)
            if cap is not None and isinstance(c, AttnCache):
                cur = c.k.shape[2]  # (count, B, S, Kv, hd)
                if cur < cap:
                    pad = [(0, 0)] * c.k.ndim
                    pad[2] = (0, cap - cur)
                    c = AttnCache(k=jnp.pad(c.k, pad), v=jnp.pad(c.v, pad))
            elif isinstance(c, LatentCache) and c.latent.shape[2] < s_ctx:
                # (count, B, S, width): grows like a full-context cache;
                # the paged pool leaves it dense
                pad = [(0, 0), (0, 0), (0, s_ctx - c.latent.shape[2]),
                       (0, 0)]
                c = LatentCache(*(jnp.pad(x, pad) for x in c))
            new_seg[f"pos{j}"] = c
        out[f"seg{i}"] = new_seg
    return out


# ---------------------------------------------------------- paged cache

def attn_cache_walk(cfg: ModelConfig, s_ctx: int):
    """Yield ``(seg_key, pos_key, kind, cap)`` for every growable
    attention sublayer (the capacity classes of the paged pool);
    cross-attention (fixed encoder length) and recurrent state are
    excluded."""
    for i, seg in enumerate(cfg.segments):
        for j, kind in enumerate(seg.pattern):
            cap = _attn_capacity(kind, cfg, s_ctx)
            if cap is not None:
                yield f"seg{i}", f"pos{j}", kind, cap


def paged_classes(cfg: ModelConfig, batch: int, s_ctx: int, *,
                  page_size: int,
                  num_pages: int | None = None) -> dict[int, int]:
    """Map each capacity class (attn full-context vs local ring) to its
    per-layer pool size in pages.  Default is full capacity plus the
    reserved trash page — functionally lossless; smaller pools trade
    admission backpressure for memory."""
    caps = sorted({cap for *_, cap in attn_cache_walk(cfg, s_ctx)})
    return {cap: (num_pages if num_pages is not None
                  else 1 + batch * paged_kv.num_logical_pages(
                      cap, page_size))
            for cap in caps}


def init_paged_cache(cfg: ModelConfig, batch: int, s_ctx: int, *,
                     page_size: int, quant: str | None = None,
                     num_pages: int | None = None,
                     dtype=jnp.bfloat16) -> dict:
    """``api.init_cache`` with every attention sublayer's dense
    ``AttnCache`` replaced by a stacked ``PagedKVCache``.

    Pool arrays gain the same leading ``(count,)`` layer-stack dim the
    dense leaves carry, so the per-segment ``lax.scan`` slices one pool
    per layer; every table entry starts on the trash page (0) — the
    engine owns allocation (``launch/serve.py``)."""
    cache = api.init_cache(cfg, batch, s_ctx, dtype)
    classes = paged_classes(cfg, batch, s_ctx, page_size=page_size,
                            num_pages=num_pages)
    for seg_key, pos_key, kind, cap in attn_cache_walk(cfg, s_ctx):
        count = cache[seg_key][pos_key].k.shape[0]
        pool = paged_kv.init_paged(
            batch, cap, cfg.num_kv_heads, cfg.head_dim,
            page_size=page_size, num_pages=classes[cap], quant=quant,
            dtype=dtype)

        def stack(x):
            return (None if x is None
                    else jnp.broadcast_to(x, (count, *x.shape)))

        cache[seg_key][pos_key] = PagedKVCache(
            k_pages=stack(pool.k_pages), v_pages=stack(pool.v_pages),
            page_table=stack(pool.page_table),
            k_scale=stack(pool.k_scale), v_scale=stack(pool.v_scale),
            s_cache=cap)
    return cache


def make_prefill(cfg: ModelConfig, policy: Policy, *,
                 s_ctx: int, remat: bool = False):
    """prefill(params, batch) -> (next-token logits, capacity cache)."""

    def prefill(params, batch):
        logits, cache = api.prefill(params, batch, cfg, policy=policy,
                                    remat=remat)
        return logits, pad_cache(cache, cfg, s_ctx)

    return prefill


def make_decode(cfg: ModelConfig, policy: Policy):
    """decode(params, cache, tokens (B,1), pos (B,)) -> (logits, cache).

    ``pos`` is the per-row position vector; a scalar broadcasts.
    """

    def decode(params, cache, tokens, pos):
        return api.decode(params, cache, tokens, pos, cfg, policy=policy)

    return decode


def make_engine_tick(cfg: ModelConfig, policy: Policy, *,
                     eos_id: int, max_ctx: int):
    """One continuous-batching engine tick, fully jit-compatible.

    tick(params, cache, last_tok (B,), pos (B,), active (B,) bool,
         remaining (B,)) -> (cache, next_tok, pos, remaining, active,
                             finished)

    Decodes one token for EVERY slot at its own position, then applies
    the per-slot lifecycle masks in-graph: inactive rows keep their
    state frozen (their decode output is discarded), active rows advance
    their position, burn one remaining-token credit, and finish on EOS,
    token-budget exhaustion, or context exhaustion. The host only ever
    reads back the small (B,) vectors — no per-token cache surgery or
    logits transfer on the hot path.

    The engine jits it donating ``cache`` and the four vectors
    (arguments 1-5, never ``params``), so XLA writes each slot's new KV
    row into the cache it reads instead of copying the whole cache.
    """

    def tick(params, cache, last_tok, pos, active, remaining):
        logits, cache = api.decode(
            params, cache, last_tok[:, None], pos, cfg, policy=policy)
        sampled = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        nxt = jnp.where(active, sampled, last_tok)
        new_pos = jnp.where(active, pos + 1, pos)
        new_rem = jnp.where(active, remaining - 1, remaining)
        finished = active & ((nxt == eos_id) | (new_rem <= 0)
                             | (new_pos >= max_ctx - 1))
        return cache, nxt, new_pos, new_rem, active & ~finished, finished

    return tick


def make_slot_splice():
    """Admission of one prefilled request into one slot, jit-compatible.

    splice(cache, cache1, slot, last_tok (B,), pos (B,), active (B,),
           remaining (B,), first_tok, new_pos, new_remaining)
        -> (cache, last_tok, pos, active, remaining)

    ``cache1`` is the prefill's batch-1 cache.  Each of its leaves with
    at least two dims goes into the batch leaf at ``slot`` along axis 1
    (leaves are (count, B, ...) stacked per segment), cast to the batch
    leaf's dtype; other leaves keep the batch leaf as it is.  The slot
    then takes the request's next input token, position and remaining
    budget, and becomes active.  ``slot`` and the three values are
    traced int32 scalars, so one program serves every slot.

    The engine jits it donating ``cache`` and the four vectors, so XLA
    writes the slot's rows in place instead of a new whole cache.
    """

    def splice(cache, cache1, slot, last_tok, pos, active, remaining,
               first_tok, new_pos, new_remaining):
        def put(full, one):
            if getattr(one, "ndim", 0) < 2:
                return full
            return jax.lax.dynamic_update_index_in_dim(
                full, one[:, 0].astype(full.dtype), slot, axis=1)

        return (jax.tree.map(put, cache, cache1),
                last_tok.at[slot].set(first_tok),
                pos.at[slot].set(new_pos),
                active.at[slot].set(True),
                remaining.at[slot].set(new_remaining))

    return splice


# ------------------------------------------------------------- abstract

def abstract_params(cfg: ModelConfig) -> Any:
    """ShapeDtypeStruct pytree of the params (no allocation)."""
    return jax.eval_shape(
        lambda: api.init_params(jax.random.PRNGKey(0), cfg))


def abstract_cache(cfg: ModelConfig, batch: int, s_ctx: int,
                   dtype=jnp.bfloat16) -> Any:
    """ShapeDtypeStruct pytree of a full-capacity decode cache."""
    return jax.eval_shape(
        lambda: api.init_cache(cfg, batch, s_ctx, dtype))
