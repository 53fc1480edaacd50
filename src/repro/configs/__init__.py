"""Architecture registry + abstract input specs for the dry-run.

``get_config(arch)`` / ``get_smoke(arch)`` return the full / reduced
``ModelConfig``; ``input_specs(cfg, shape)`` returns weak-type-correct
``ShapeDtypeStruct`` stand-ins for every model input of that (arch x
shape) cell — shardable, zero allocation.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from repro.configs.base import LM_SHAPES, ModelConfig, ShapeSpec

__all__ = ["ARCHS", "get_config", "get_smoke", "input_specs", "LM_SHAPES"]

_MODULES = {
    "rwkv6-7b": "rwkv6_7b",
    "nemotron-4-340b": "nemotron4_340b",
    "starcoder2-15b": "starcoder2_15b",
    "gemma3-1b": "gemma3_1b",
    "command-r-35b": "command_r_35b",
    "zamba2-7b": "zamba2_7b",
    "mixtral-8x7b": "mixtral_8x7b",
    "dbrx-132b": "dbrx_132b",
    "whisper-medium": "whisper_medium",
    "internvl2-76b": "internvl2_76b",
    "deepseek-v2-lite": "deepseek_v2_lite",
}

ARCHS: tuple[str, ...] = tuple(_MODULES)


def _module(arch: str):
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; one of {list(_MODULES)}")
    return importlib.import_module(f"repro.configs.{_MODULES[arch]}")


def get_config(arch: str) -> ModelConfig:
    return _module(arch).CONFIG


def get_smoke(arch: str) -> ModelConfig:
    return _module(arch).smoke()


def input_specs(cfg: ModelConfig, shape: ShapeSpec | str) -> dict:
    """Abstract batch inputs for one (arch x shape) cell.

    train/prefill: full-sequence tokens (+ stubbed modality embeddings).
    decode: one new token per sequence (the KV cache / recurrent state is
    a separate argument built by runtime.serve_step.abstract_cache).
    """
    if isinstance(shape, str):
        shape = LM_SHAPES[shape]
    b = shape.global_batch
    f32 = jnp.float32

    def tok(s):
        return jax.ShapeDtypeStruct((b, s), jnp.int32)

    if shape.mode in ("train", "prefill"):
        s = shape.seq_len
        specs: dict = {"tokens": tok(s)}
        if shape.mode == "train":
            specs["labels"] = tok(s)
        if cfg.family == "audio":
            specs["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder_seq, cfg.d_model), f32)
        if cfg.family == "vlm":
            specs["image_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.num_image_tokens, cfg.d_model), f32)
        return specs

    if shape.mode == "decode":
        return {
            "tokens": tok(1),
            # per-slot position vector: continuous batching admits rows
            # at different ticks, so every row has its own position
            "pos": jax.ShapeDtypeStruct((b,), jnp.int32),
        }
    raise ValueError(f"unknown mode {shape.mode!r}")
