"""Unified decoder-only LM over per-layer "segment" programs.

A model is ``cfg.segments``: each Segment is `count` repetitions of a
sublayer pattern (e.g. ("attn","mlp"), or gemma3's 5-local:1-global
period). Per-segment params are STACKED over `count` and executed with
``lax.scan`` — one traced period per segment keeps the HLO small enough
that all 80 (arch x shape x mesh) dry-run compiles stay fast, and gives
the FSDP all-gather-per-layer structure XLA expects.

zamba2's `shared_attn` blocks read their params from a single shared
tree (closure), not from the scanned stack — the paper-pool's
"shared attention" semantics — while their KV caches remain
per-occurrence (stacked).

`mla` sublayers (DeepSeek-V2 latent attention, ``models/mla.py``) keep
a ``LatentCache`` of ``kv_lora_rank + qk_rope_dim`` values per token
(two leaves) instead of per-head K and V.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, Segment
from repro.core.precision import PrecisionPolicy
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as M
from repro.models import rwkv as R
from repro.models import ssm as S
from repro.models.attention import AttnCache, attention

__all__ = ["init_params", "forward", "init_cache", "lm_loss"]

_ATTN_KINDS = ("attn", "attn_local", "cross_attn")


# ==================================================================== init

def _init_sublayer(key, kind: str, cfg: ModelConfig,
                   stack: tuple[int, ...]) -> dict:
    from repro.models.attention import init_attn
    kn, kb = jax.random.split(key)
    if kind in _ATTN_KINDS:
        return {
            "norm": L.init_rmsnorm(cfg.d_model, stack=stack),
            **init_attn(kb, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                        cfg.head_dim, bias=cfg.qkv_bias, stack=stack),
        }
    if kind == "mla":
        return {"norm": L.init_rmsnorm(cfg.d_model, stack=stack),
                **MLA.init_mla(kb, cfg, stack=stack)}
    if kind == "mlp":
        return {
            "norm": L.init_rmsnorm(cfg.d_model, stack=stack),
            **L.init_mlp(kb, cfg.d_model, cfg.d_ff, cfg.mlp_kind,
                         bias=cfg.mlp_bias, stack=stack),
        }
    if kind == "moe":
        return {
            "norm": L.init_rmsnorm(cfg.d_model, stack=stack),
            **M.init_moe(kb, cfg.d_model, cfg.moe_d_ff or cfg.d_ff,
                         cfg.num_experts, cfg.mlp_kind, stack=stack,
                         router_experts=cfg.router_experts,
                         shared_d_ff=cfg.shared_d_ff),
        }
    if kind == "mamba2":
        return S.init_mamba2(kb, cfg.d_model, cfg.ssm_head_dim,
                             cfg.ssm_state, cfg.conv_width, stack=stack)
    if kind == "rwkv6":
        return R.init_rwkv6(kb, cfg.d_model, cfg.d_ff, cfg.rwkv_head_dim,
                            stack=stack)
    if kind == "shared_attn":
        return {}  # params live in the shared tree, not the stack
    raise ValueError(f"unknown sublayer kind {kind!r}")


def init_segment(key, seg: Segment, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, len(seg.pattern))
    return {
        f"pos{i}": _init_sublayer(keys[i], kind, cfg, stack=(seg.count,))
        for i, kind in enumerate(seg.pattern)
    }


def _has_shared(cfg: ModelConfig) -> bool:
    return any("shared_attn" in s.pattern for s in cfg.segments)


def init_params(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, len(cfg.segments) + 4)
    params: dict[str, Any] = {
        "embed": L.init_embedding(ks[0], cfg.vocab_size, cfg.d_model),
        "final_norm": L.init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.init_embedding(ks[1], cfg.vocab_size, cfg.d_model)
    for i, seg in enumerate(cfg.segments):
        params[f"seg{i}"] = init_segment(ks[2 + i], seg, cfg)
    if _has_shared(cfg):
        kk = jax.random.split(ks[-1], 3)
        from repro.models.attention import init_attn
        params["shared"] = {
            "norm1": L.init_rmsnorm(cfg.d_model),
            "attn": init_attn(kk[0], cfg.d_model, cfg.num_heads,
                              cfg.num_kv_heads, cfg.head_dim),
            "norm2": L.init_rmsnorm(cfg.d_model),
            "mlp": L.init_mlp(kk[1], cfg.d_model, cfg.d_ff, cfg.mlp_kind),
        }
    if cfg.rope_theta is None and cfg.family != "ssm":
        # learned positional embeddings (whisper-style)
        max_pos = max(32_768, cfg.encoder_seq)
        params["pos_embed"] = {"table": 0.02 * jax.random.normal(
            ks[-2], (max_pos, cfg.d_model)).astype(jnp.float32)}
    return params


# =================================================================== cache

def _zero_kv(shape: tuple[int, ...], dtype) -> AttnCache:
    # K and V get buffers of their own: a program that donates the
    # cache cannot take one buffer twice
    return AttnCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def _init_sublayer_cache(kind: str, cfg: ModelConfig, batch: int,
                         s_ctx: int, stack: tuple[int, ...], dtype):
    if kind in ("attn", "attn_local"):
        s_c = s_ctx if (kind == "attn" or cfg.window is None) \
            else min(s_ctx, cfg.window)
        return _zero_kv((*stack, batch, s_c, cfg.num_kv_heads,
                         cfg.head_dim), dtype)
    if kind == "mla":
        return MLA.LatentCache(
            latent=jnp.zeros((*stack, batch, s_ctx, cfg.kv_lora_rank), dtype),
            k_rope=jnp.zeros((*stack, batch, s_ctx, cfg.qk_rope_dim), dtype))
    if kind == "cross_attn":
        return _zero_kv((*stack, batch, cfg.encoder_seq, cfg.num_kv_heads,
                         cfg.head_dim), dtype)
    if kind == "shared_attn":
        return _zero_kv((*stack, batch, s_ctx, cfg.num_kv_heads,
                         cfg.head_dim), dtype)
    if kind == "mamba2":
        st = S.init_mamba_state(batch, cfg.d_model, cfg.ssm_head_dim,
                                cfg.ssm_state, cfg.conv_width)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (*stack, *x.shape)), st)
    if kind == "rwkv6":
        st = R.init_rwkv_state(batch, cfg.d_model, cfg.rwkv_head_dim)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (*stack, *x.shape)), st)
    return {}


def init_cache(cfg: ModelConfig, batch: int, s_ctx: int,
               dtype=jnp.bfloat16) -> dict:
    """Pre-allocated decode cache for every stateful sublayer."""
    cache: dict[str, Any] = {}
    for i, seg in enumerate(cfg.segments):
        cache[f"seg{i}"] = {
            f"pos{j}": _init_sublayer_cache(kind, cfg, batch, s_ctx,
                                            (seg.count,), dtype)
            for j, kind in enumerate(seg.pattern)
        }
    return cache


# ================================================================= forward

def _apply_sublayer(kind: str, p: dict, x: jax.Array, *, cfg: ModelConfig,
                    policy: PrecisionPolicy, mode: str, cache, pos,
                    shared: dict | None, enc_x: jax.Array | None,
                    layer=None):
    """Returns (x, new_cache, aux_loss).  ``layer``: the index into a
    stacked cache this sublayer decodes against in place (`mla`)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "attn_local") or kind == "shared_attn":
        if kind == "shared_attn":
            ap = shared["attn"]
            xn = L.rmsnorm(shared["norm1"], x, cfg.norm_eps)
        else:
            ap = p
            xn = L.rmsnorm(p["norm"], x, cfg.norm_eps)
        out, new_cache = attention(
            ap, xn, mode=mode, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            policy=policy.for_("attention"), rope_theta=cfg.rope_theta,
            window=cfg.window if kind == "attn_local" else None,
            softcap=cfg.attn_logit_softcap, causal=(mode != "encode"),
            cache=cache if mode == "decode" else None, pos=pos)
        x = x + out
        if kind == "shared_attn":
            xn2 = L.rmsnorm(shared["norm2"], x, cfg.norm_eps)
            x = x + L.mlp(shared["mlp"], xn2, cfg.mlp_kind,
                          policy.for_("mlp"))
        if mode in ("train", "encode"):
            new_cache = {}
        elif new_cache is None:
            new_cache = cache if cache is not None else {}
        return x, new_cache, aux
    if kind == "cross_attn":
        xn = L.rmsnorm(p["norm"], x, cfg.norm_eps)
        if mode == "decode":
            ckv = cache
        else:  # train/prefill: project encoder stream once
            b, se, _ = enc_x.shape
            kc = L.linear(p["wk"], enc_x, policy.for_("attention")).reshape(
                b, se, cfg.num_kv_heads, cfg.head_dim).astype(x.dtype)
            vc = L.linear(p["wv"], enc_x, policy.for_("attention")).reshape(
                b, se, cfg.num_kv_heads, cfg.head_dim).astype(x.dtype)
            ckv = AttnCache(k=kc, v=vc)
        out, _ = attention(
            p, xn, mode=mode, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            policy=policy.for_("attention"), rope_theta=None,
            cross_kv=ckv, pos=pos)
        new_cache = ckv if mode in ("prefill", "decode") else {}
        return x + out, new_cache, aux
    if kind == "mla":
        xn = L.rmsnorm(p["norm"], x, cfg.norm_eps)
        out, new_cache = MLA.mla_attention(
            p, xn, cfg=cfg, mode=mode, policy=policy.for_("attention"),
            cache=cache if mode == "decode" else None, pos=pos, layer=layer)
        return x + out, ({} if new_cache is None else new_cache), aux
    if kind == "mlp":
        xn = L.rmsnorm(p["norm"], x, cfg.norm_eps)
        return x + L.mlp(p, xn, cfg.mlp_kind, policy.for_("mlp")), {}, aux
    if kind == "moe":
        xn = L.rmsnorm(p["norm"], x, cfg.norm_eps)
        out, aux = M.moe_ffn(
            p, xn, num_experts=cfg.num_experts, top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor, mlp_kind=cfg.mlp_kind,
            policy=policy.for_("moe"), dropless=(mode != "train"),
            first_expert=cfg.first_expert)
        return x + out, {}, aux
    if kind == "mamba2":
        x, new_state = S.mamba2_layer(
            p, x, head_dim=cfg.ssm_head_dim, ssm_state=cfg.ssm_state,
            conv_width=cfg.conv_width, policy=policy.for_("mlp"),
            chunk=cfg.ssm_chunk, state=cache if mode == "decode" else None,
            norm_eps=cfg.norm_eps, return_state=(mode == "prefill"))
        return x, (new_state if new_state is not None else {}), aux
    if kind == "rwkv6":
        x, new_state = R.rwkv6_layer(
            p, x, head_dim=cfg.rwkv_head_dim, policy=policy.for_("mlp"),
            state=cache if mode == "decode" else None, chunk=cfg.rwkv_chunk,
            norm_eps=cfg.norm_eps, return_state=(mode == "prefill"))
        return x, (new_state if new_state is not None else {}), aux
    raise ValueError(f"unknown sublayer kind {kind!r}")


def _apply_segment(seg_params: dict, seg: Segment, x: jax.Array, *,
                   cfg: ModelConfig, policy: PrecisionPolicy, mode: str,
                   seg_cache: dict | None, pos, shared, enc_x,
                   remat: bool = False):
    """Scan `seg.count` periods of the pattern. Returns (x, new_cache, aux).

    In decode, latent (`mla`) caches ride in the scan's carry with the
    layer index: each layer writes its new rows into the stacked buffer
    and reads its own slice there, where a cache scanned over as xs/ys
    is copied whole on every call."""
    n_pos = len(seg.pattern)
    has_cache = seg_cache is not None
    carried = tuple(f"pos{j}" for j, kind in enumerate(seg.pattern)
                    if kind == "mla") if has_cache and mode == "decode" \
        else ()

    def period(carry, xs):
        from repro.runtime.act_sharding import constrain
        x, aux, *held = carry
        p_stack, c_stack = xs
        stacks, layer = held if held else ({}, None)
        new_caches = {}
        for j, kind in enumerate(seg.pattern):
            key = f"pos{j}"
            c_j = stacks.get(key, c_stack.get(key) if has_cache else None)
            x, nc, a = _apply_sublayer(
                kind, p_stack[key], x, cfg=cfg, policy=policy,
                mode=mode, cache=c_j, pos=pos, shared=shared, enc_x=enc_x,
                layer=layer if key in stacks else None)
            x = constrain(x, "residual")  # pin (B: dp, S, D: replicated)
            if key in stacks:
                stacks, nc = {**stacks, key: nc}, {}
            new_caches[key] = nc
            aux = aux + a
        if held:
            return (x, aux, stacks, layer + 1), new_caches
        return (x, aux), new_caches

    body = jax.checkpoint(period) if remat else period
    xs = (seg_params, {k: ({} if k in carried else c)
                       for k, c in seg_cache.items()} if has_cache else
          {f"pos{j}": {} for j in range(n_pos)})
    init = (x, jnp.zeros((), jnp.float32))
    if carried:
        init += ({k: seg_cache[k] for k in carried},
                 jnp.zeros((), jnp.int32))
    out, new_cache = jax.lax.scan(body, init, xs)
    x, aux = out[:2]
    if carried:
        new_cache.update(out[2])
    return x, new_cache, aux


def forward(params: dict, tokens: jax.Array, cfg: ModelConfig, *,
            policy: PrecisionPolicy, mode: str = "train",
            cache: dict | None = None, pos: jax.Array | None = None,
            extra_embeds: jax.Array | None = None,
            enc_x: jax.Array | None = None, remat: bool = False,
            segments: tuple[Segment, ...] | None = None,
            seg_prefix: str = "seg", pos_embed_key: str = "pos_embed",
            final_norm_key: str = "final_norm"):
    """Run the LM stack.

    tokens: (B, S) int32. extra_embeds: (B, S_img, D) prepended (VLM).
    mode: train | prefill | decode | encode (encode = non-causal, no loss).
    decode: ``pos`` is the per-row position vector (B,) — rows admitted
    at different engine ticks decode at different absolute positions.
    Returns (logits | hidden, new_cache, aux_loss). For mode="encode"
    returns hidden states instead of logits.
    """
    from repro.runtime.act_sharding import constrain
    dtype = jnp.dtype(cfg.activation_dtype)
    segs = cfg.segments if segments is None else segments
    if tokens is not None:
        x = L.embed(params["embed"], tokens, dtype)
        if extra_embeds is not None:
            x = jnp.concatenate([extra_embeds.astype(dtype), x], axis=1)
    else:
        x = extra_embeds.astype(dtype)  # pure-embedding input (whisper enc)
    x = constrain(x, "residual")

    if pos_embed_key in params and cfg.rope_theta is None:
        s = x.shape[1]
        if mode == "decode":
            # per-row positions (B,): gather one embedding per slot
            pe = params[pos_embed_key]["table"][
                jnp.broadcast_to(pos, (x.shape[0],))]       # (B, D)
            x = x + pe.astype(dtype)[:, None, :]
        else:
            pe = params[pos_embed_key]["table"][:s]
            x = x + pe.astype(dtype)[None]

    shared = params.get("shared")
    new_cache: dict[str, Any] = {}
    aux = jnp.zeros((), jnp.float32)
    for i, seg in enumerate(segs):
        key = f"{seg_prefix}{i}"
        seg_cache = cache.get(key) if cache is not None else None
        x, nc, a = _apply_segment(
            params[key], seg, x, cfg=cfg, policy=policy, mode=mode,
            seg_cache=seg_cache, pos=pos, shared=shared, enc_x=enc_x,
            remat=remat)
        new_cache[key] = nc
        aux = aux + a

    x = L.rmsnorm(params[final_norm_key], x, cfg.norm_eps)
    if mode == "encode":
        return x, new_cache, aux
    table = params["embed" if cfg.tie_embeddings else "unembed"]
    logits = L.unembed(table, x, policy.for_("logits"))
    return logits, new_cache, aux


def lm_loss(logits: jax.Array, labels: jax.Array,
            mask: jax.Array | None = None) -> jax.Array:
    """Next-token cross entropy in fp32 (labels already shifted).

    The label logit is extracted with a one-hot CONTRACTION, not
    ``take_along_axis``: a gather across the vocab axis cannot be
    partitioned when logits are vocab-sharded (TP over 'model') and
    XLA falls back to all-gathering the full (B, S, V) logits — 34 GB
    per microbatch for the 262k-vocab cells (§Perf iteration A3). The
    one-hot compare+select fuses into the reduction and keeps every
    shard local (partial sums all-reduce a (B, S) tensor instead).
    """
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    onehot = labels[..., None] == jax.lax.broadcasted_iota(
        jnp.int32, (1,) * labels.ndim + (logits.shape[-1],), labels.ndim)
    ll = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    nll = logz - ll
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()
