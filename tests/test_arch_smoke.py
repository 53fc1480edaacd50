"""Per-architecture smoke tests: every assigned arch instantiates a
REDUCED config of the same family and runs one forward/train step on CPU,
asserting output shapes and no NaNs. Full configs are exercised only by
the dry-run (launch/dryrun.py) — never allocated here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke, input_specs
from repro.configs.base import LM_SHAPES
from repro.core.precision import PrecisionPolicy
from repro.models import api
from repro.optim import adamw
from repro.runtime.train_step import make_train_step

POLICY = PrecisionPolicy.uniform("bf16")
B, S = 2, 24


def _batch(cfg, key, s=S):
    ks = jax.random.split(key, 3)
    batch = {
        "tokens": jax.random.randint(ks[0], (B, s), 0, cfg.vocab_size),
        "labels": jax.random.randint(ks[1], (B, s), 0, cfg.vocab_size),
    }
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            ks[2], (B, cfg.encoder_seq, cfg.d_model), jnp.float32)
    if cfg.family == "vlm":
        batch["image_embeds"] = jax.random.normal(
            ks[2], (B, cfg.num_image_tokens, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
class TestArchSmoke:
    def test_full_config_matches_assignment(self, arch):
        """The FULL config carries the exact assigned hyperparameters."""
        cfg = get_config(arch)
        assigned = {
            "rwkv6-7b": dict(num_layers=32, d_model=4096, d_ff=14336,
                             vocab_size=65536),
            "nemotron-4-340b": dict(num_layers=96, d_model=18432,
                                    num_heads=96, num_kv_heads=8,
                                    d_ff=73728, vocab_size=256000),
            "starcoder2-15b": dict(num_layers=40, d_model=6144, num_heads=48,
                                   num_kv_heads=4, d_ff=24576,
                                   vocab_size=49152),
            "gemma3-1b": dict(num_layers=26, d_model=1152, num_heads=4,
                              num_kv_heads=1, d_ff=6912, vocab_size=262144),
            "command-r-35b": dict(num_layers=40, d_model=8192, num_heads=64,
                                  num_kv_heads=8, d_ff=22528,
                                  vocab_size=256000),
            "zamba2-7b": dict(num_layers=81, d_model=3584, num_heads=32,
                              num_kv_heads=32, d_ff=14336, vocab_size=32000,
                              ssm_state=64),
            "mixtral-8x7b": dict(num_layers=32, d_model=4096, num_heads=32,
                                 num_kv_heads=8, d_ff=14336,
                                 vocab_size=32000, num_experts=8, top_k=2),
            "dbrx-132b": dict(num_layers=40, d_model=6144, num_heads=48,
                              num_kv_heads=8, d_ff=10752, vocab_size=100352,
                              num_experts=16, top_k=4),
            "whisper-medium": dict(num_layers=24, d_model=1024, num_heads=16,
                                   num_kv_heads=16, d_ff=4096,
                                   vocab_size=51865, encoder_layers=24),
            "internvl2-76b": dict(num_layers=80, d_model=8192, num_heads=64,
                                  num_kv_heads=8, d_ff=28672,
                                  vocab_size=128256),
            "deepseek-v2-lite": dict(num_layers=27, d_model=2048,
                                     num_heads=16, num_kv_heads=16,
                                     head_dim=128, kv_lora_rank=512,
                                     qk_rope_dim=64, v_head_dim=128,
                                     d_ff=10944, moe_d_ff=1408,
                                     shared_d_ff=2816, vocab_size=102400,
                                     num_experts=64, num_routed_experts=64,
                                     top_k=6),
        }[arch]
        for k, v in assigned.items():
            assert getattr(cfg, k) == v, f"{arch}.{k}: {getattr(cfg, k)} != {v}"

    def test_train_step_no_nans(self, arch):
        cfg = get_smoke(arch)
        key = jax.random.PRNGKey(hash(arch) % 2 ** 31)
        params = api.init_params(key, cfg)
        batch = _batch(cfg, key)
        opt = adamw.init(params)
        step = jax.jit(make_train_step(
            cfg, adamw.AdamWConfig(), POLICY, microbatches=1, remat=False))
        new_params, new_opt, metrics = step(params, opt, batch)
        assert np.isfinite(float(metrics["loss"])), f"{arch} loss NaN"
        assert np.isfinite(float(metrics["grad_norm"]))
        assert float(metrics["grad_norm"]) > 0.0, f"{arch} zero grads"
        assert int(new_opt.step) == 1
        # params actually moved
        moved = any(
            not np.allclose(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(params),
                            jax.tree.leaves(new_params)))
        assert moved, f"{arch} params unchanged after a step"

    def test_forward_shapes(self, arch):
        cfg = get_smoke(arch)
        key = jax.random.PRNGKey(1)
        params = api.init_params(key, cfg)
        batch = _batch(cfg, key)
        loss, metrics = api.loss_fn(params, batch, cfg, policy=POLICY)
        assert loss.shape == ()
        assert np.isfinite(float(loss))

    def test_prefill_then_decode_step(self, arch):
        """Every arch has a decode path (per the assignment: no arch skips
        decode shapes)."""
        cfg = get_smoke(arch)
        key = jax.random.PRNGKey(2)
        params = api.init_params(key, cfg)
        batch = _batch(cfg, key)
        logits, cache = api.prefill(params, batch, cfg, policy=POLICY)
        assert logits.shape[0] == B and logits.shape[-1] == cfg.vocab_size
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        pos = jnp.full(
            (B,),
            S + (cfg.num_image_tokens if cfg.family == "vlm" else 0),
            jnp.int32)
        logits2, cache2 = api.decode(params, cache, nxt, pos, cfg,
                                     policy=POLICY)
        assert logits2.shape == (B, 1, cfg.vocab_size)
        assert np.all(np.isfinite(np.asarray(logits2, np.float32)))

    def test_input_specs_cover_shapes(self, arch):
        cfg = get_config(arch)
        for name in cfg.supported_shapes:
            specs = input_specs(cfg, LM_SHAPES[name])
            assert "tokens" in specs
            for v in specs.values():
                assert isinstance(v, jax.ShapeDtypeStruct)

    def test_long_500k_support_matches_design(self, arch):
        """Sub-quadratic archs run long_500k; pure full-attention skip."""
        cfg = get_config(arch)
        runs_long = "long_500k" in cfg.supported_shapes
        expected = arch in ("rwkv6-7b", "zamba2-7b", "gemma3-1b",
                            "mixtral-8x7b")
        assert runs_long == expected
