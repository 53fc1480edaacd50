"""Serve-step builders: prefill and single-token decode with padded,
shardable caches.

``prefill`` ingests the context and emits a cache PADDED to the decode
capacity (attention caches grow in place afterwards; ring-buffer local
caches are already window-sized; recurrent states are O(1)). ``decode``
is the cell lowered for the ``decode_32k`` / ``long_500k`` dry-runs —
one new token against the full-capacity cache, NOT a train step.

``bf16_read_leaves`` finds, from the jaxprs of the tick and the prefill,
the float32 weights that every read rounds to bf16 first, and
``round_leaves`` rounds them once, so the engine serves those in bf16.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Literal

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
           "init_paged_cache", "bf16_read_leaves", "round_leaves"]

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


# ------------------------------------------------------- served weights

# Primitives that move or index their first operand's values and change
# none of them, so rounding commutes with them: a read through one is
# the same read of the rounded leaf.  Their other operands are indices.
_LAYOUT = frozenset({"reshape", "squeeze", "transpose", "broadcast_in_dim",
                     "slice", "dynamic_slice", "gather"})
# Calls whose body takes the call's operands one for one.
_CALLS = frozenset({"jit", "closed_call", "remat2", "custom_jvp_call",
                    "custom_vjp_call", "shard_map"})
_NONE: frozenset = frozenset()


def _union(sets) -> frozenset:
    return _NONE.union(*sets)


def _reads(jaxpr, raw: list, seen: dict) -> list:
    """Follow the inputs' unrounded values through one jaxpr.

    ``raw[i]`` is the set of top-level inputs whose unrounded values
    ``jaxpr.invars[i]`` carries.  A read that rounds such a value to
    bf16 adds its inputs to ``seen["rounded"]``; any other read adds
    them to ``seen["kept"]``.  Returns the sets that the outvars carry.
    """
    env = dict(zip(jaxpr.invars, raw))

    def get(v):
        return _NONE if isinstance(v, Literal) else env.get(v, _NONE)

    for eqn in jaxpr.eqns:
        ins = [get(v) for v in eqn.invars]
        if not any(ins):
            continue
        name = eqn.primitive.name
        if name == "convert_element_type":
            new = jnp.dtype(eqn.params["new_dtype"])
            if new == jnp.bfloat16:
                seen["rounded"] |= ins[0]
                continue
            if new != eqn.invars[0].aval.dtype:
                seen["kept"] |= ins[0]
                continue
            outs = ins
        elif name in _LAYOUT:
            seen["kept"] |= _union(ins[1:])
            outs = ins[:1]
        else:
            outs = _call_reads(eqn, ins, seen)
            if outs is None:
                seen["kept"] |= _union(ins)
                continue
        env.update(zip(eqn.outvars, outs))
    return [get(v) for v in jaxpr.outvars]


def _call_reads(eqn, ins: list, seen: dict) -> list | None:
    """``_reads`` into the bodies of a call or a loop; None for any
    other primitive.  A value carried round a loop is kept: the loop may
    change it."""
    p, name = eqn.params, eqn.primitive.name
    if name == "scan":
        nc, nk = p["num_consts"], p["num_carry"]
        seen["kept"] |= _union(ins[nc:nc + nk])
        outs = _reads(p["jaxpr"].jaxpr,
                      ins[:nc] + [_NONE] * nk + ins[nc + nk:], seen)
        seen["kept"] |= _union(outs[:nk])
        return [_NONE] * nk + outs[nk:]
    if name == "while":
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        carry = [_NONE] * (len(ins) - cn - bn)
        seen["kept"] |= _union(ins[cn + bn:])
        _reads(p["cond_jaxpr"].jaxpr, ins[:cn] + carry, seen)
        seen["kept"] |= _union(
            _reads(p["body_jaxpr"].jaxpr, ins[cn:cn + bn] + carry, seen))
        return [_NONE] * len(eqn.outvars)
    if name == "cond":
        seen["kept"] |= ins[0]
        per = [_reads(b.jaxpr, ins[1:], seen) for b in p["branches"]]
        return [_union(o) for o in zip(*per)]
    body = p.get("jaxpr", p.get("call_jaxpr"))
    if name not in _CALLS or body is None:
        return None
    if isinstance(body, ClosedJaxpr):
        body = body.jaxpr
    return _reads(body, ins, seen)


def bf16_read_leaves(params, programs) -> Any:
    """Which float32 leaves of ``params`` every read rounds to bf16.

    ``programs`` is a sequence of ``(fn, args)``, each called as
    ``fn(params, *args)``.  A leaf qualifies when each of its reads in
    every program, followed through layout-only ops and into nested
    calls and loops, is a cast to bf16, and at least one read is.
    Returns a tree of bools shaped like ``params``.
    """
    leaves, treedef = jax.tree.flatten(params)
    rounded, kept = set(), set()
    for fn, args in programs:
        closed = jax.make_jaxpr(fn)(params, *args)
        seen = {"rounded": set(), "kept": set()}
        outs = _reads(closed.jaxpr, [frozenset({i}) for i in
                                     range(len(closed.jaxpr.invars))],
                      seen)
        rounded |= seen["rounded"]
        kept |= seen["kept"] | _union(outs)
    return treedef.unflatten([
        i in rounded and i not in kept and x.dtype == jnp.float32
        for i, x in enumerate(leaves)])


def round_leaves(params, which) -> Any:
    """``params`` with each leaf that ``which`` marks cast to bf16 once.

    The other leaves are the caller's own arrays.  A rounded leaf keeps
    its leaf's sharding, so a mesh places it where it placed the leaf.
    """
    leaves, treedef = jax.tree.flatten(params)
    picked = [x for x, w in zip(leaves, jax.tree.leaves(which)) if w]
    if not picked:
        return params
    # an elementwise program: each output takes its input's sharding,
    # and is committed to its devices where the input was
    done = iter(jax.jit(lambda xs: [x.astype(jnp.bfloat16) for x in xs])(
        picked))
    return treedef.unflatten([next(done) if w else x for x, w in
                              zip(leaves, jax.tree.leaves(which))])
