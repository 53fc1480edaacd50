"""Family-dispatching facade: one API for every architecture.

runtime/, launch/ and tests/ talk to models exclusively through this
module, so train_step / serve_step / dryrun are arch-agnostic.

``policy`` is a ``PrecisionPolicy`` (matmuls on XLA dots) or a
``core.ops.ExecutionPolicy`` (same precision semantics, plus the
``backends: {family: impl}`` mapping + tile routing onto the
registered Pallas kernels; the legacy ``MatmulPolicy`` subclass also
works).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.ops import ExecutionPolicy
from repro.core.precision import PrecisionPolicy
from repro.models import encdec as E
from repro.models import transformer as T
from repro.models import vlm as V

__all__ = ["init_params", "init_cache", "loss_fn", "prefill", "decode",
           "context_len"]

Policy = PrecisionPolicy | ExecutionPolicy


def init_params(key, cfg: ModelConfig) -> dict:
    if cfg.family == "audio":
        return E.init_params(key, cfg)
    return T.init_params(key, cfg)


def context_len(cfg: ModelConfig, seq_len: int) -> int:
    """Decode-cache capacity for a cell (image tokens extend the VLM ctx)."""
    if cfg.family == "vlm":
        return seq_len + cfg.num_image_tokens
    return seq_len


def init_cache(cfg: ModelConfig, batch: int, s_ctx: int,
               dtype=jnp.bfloat16) -> dict:
    return T.init_cache(cfg, batch, s_ctx, dtype)


def loss_fn(params: dict, batch: dict[str, jax.Array], cfg: ModelConfig, *,
            policy: Policy, remat: bool = False,
            aux_weight: float = 0.01) -> tuple[jax.Array, dict[str, Any]]:
    """Training loss for one (micro)batch. batch: tokens, labels,
    [frames | image_embeds]."""
    if cfg.family == "audio":
        logits, _, aux = E.forward(
            params, batch["tokens"], batch["frames"], cfg, policy=policy,
            mode="train", remat=remat)
        loss = T.lm_loss(logits, batch["labels"])
    elif cfg.family == "vlm":
        logits, _, aux = V.forward(
            params, batch["tokens"], batch["image_embeds"], cfg,
            policy=policy, mode="train", remat=remat)
        loss = V.vlm_loss(logits, batch["labels"], cfg.num_image_tokens)
    else:
        logits, _, aux = T.forward(
            params, batch["tokens"], cfg, policy=policy, mode="train",
            remat=remat)
        loss = T.lm_loss(logits, batch["labels"])
    total = loss + aux_weight * aux
    return total, {"loss": loss, "aux_loss": aux}


def prefill(params: dict, batch: dict[str, jax.Array], cfg: ModelConfig, *,
            policy: Policy, remat: bool = False):
    """Context ingestion. Returns (last-position logits, cache)."""
    if cfg.family == "audio":
        logits, cache, _ = E.forward(
            params, batch["tokens"], batch["frames"], cfg, policy=policy,
            mode="prefill", remat=remat)
    elif cfg.family == "vlm":
        logits, cache, _ = V.forward(
            params, batch["tokens"], batch["image_embeds"], cfg,
            policy=policy, mode="prefill", remat=remat)
    else:
        logits, cache, _ = T.forward(
            params, batch["tokens"], cfg, policy=policy, mode="prefill",
            remat=remat)
    return logits[:, -1:], cache


def decode(params: dict, cache: dict, tokens: jax.Array, pos: jax.Array,
           cfg: ModelConfig, *, policy: Policy):
    """One decode step: tokens (B,1), ``pos`` the PER-ROW absolute
    position vector (B,) int32 — continuous-batching slots admitted at
    different ticks decode at different positions. A scalar ``pos`` is
    accepted for convenience and broadcast to every row."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (tokens.shape[0],))
    if cfg.family == "audio":
        logits, new_cache, _ = E.forward(
            params, tokens, None, cfg, policy=policy, mode="decode",
            cache=cache, pos=pos)
    else:
        logits, new_cache, _ = T.forward(
            params, tokens, cfg, policy=policy, mode="decode",
            cache=cache, pos=pos)
    return logits, new_cache
