"""The plain reference against the program at tiny widths on the CPU,
both in float32: mixtral-style prefill then cached decode, and
starcoder2-style loss and gradients."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chip_testlib import HERE, TINY_DENSE, TINY_MOE

from benchmarks.chip import harness, weights
from repro.core.precision import PrecisionPolicy
from repro.models import api
from repro.runtime import serve_step

ref = harness.load_module(HERE / "references" / "decoder.py")
F32 = PrecisionPolicy.uniform("f32")


def _model(config, ctx):
    cell = harness.Cell(name="t", entry={"config": "t"},
                        traffic={"max_ctx": ctx}, config=config, seed=0,
                        seconds=1, precision="f32", here=HERE)
    return dataclasses.replace(harness.build_model(cell),
                               activation_dtype="float32")


def test_prefill_then_cached_decode_matches_reference():
    cfg = _model(TINY_MOE, 64)
    params = weights.make(serve_step.abstract_params(cfg), seed=3)
    rng = np.random.default_rng(0)
    seq = rng.integers(2, cfg.vocab_size, 40).astype(np.int32)
    n_prompt = 24
    prefill = jax.jit(serve_step.make_prefill(cfg, F32, s_ctx=64))
    decode = jax.jit(serve_step.make_decode(cfg, F32))
    logits, cache = prefill(
        params, {"tokens": jnp.asarray(seq[None, :n_prompt])})
    got = [logits[0, -1]]
    for t in range(n_prompt, len(seq)):
        lg, cache = decode(params, cache, jnp.asarray(seq[None, t:t + 1]),
                           jnp.asarray([t], jnp.int32))
        got.append(lg[0, -1])
    want = ref.logits(params, jnp.asarray(seq), TINY_MOE)[n_prompt - 1:]
    np.testing.assert_allclose(np.stack(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_loss_and_gradients_match_reference():
    cfg = _model(TINY_DENSE, 32)
    params = weights.make(serve_step.abstract_params(cfg), seed=5)
    rng = np.random.default_rng(1)
    stream = rng.integers(0, cfg.vocab_size, (3, 33)).astype(np.int32)
    batch = {"tokens": jnp.asarray(stream[:, :-1]),
             "labels": jnp.asarray(stream[:, 1:])}

    def prog(p):
        total, m = api.loss_fn(p, batch, cfg, policy=F32)
        return total, m["loss"]

    (_, loss), grads = jax.value_and_grad(prog, has_aux=True)(params)
    ref_loss, ref_grads = ref.loss_and_grads(
        params, batch["tokens"], batch["labels"], TINY_DENSE)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        scale = float(jnp.max(jnp.abs(r))) + 1e-12
        assert float(jnp.max(jnp.abs(g - r))) <= 1e-4 * scale


def test_moe_loss_includes_load_balancing_term():
    cfg = _model(TINY_MOE, 32)
    params = weights.make(serve_step.abstract_params(cfg), seed=9)
    stream = np.random.default_rng(2).integers(
        0, cfg.vocab_size, (2, 17)).astype(np.int32)
    batch = {"tokens": jnp.asarray(stream[:, :-1]),
             "labels": jnp.asarray(stream[:, 1:])}

    def prog(p):
        total, m = api.loss_fn(p, batch, cfg, policy=F32)
        return total, m["loss"]

    (_, loss), grads = jax.value_and_grad(prog, has_aux=True)(params)
    ref_loss, ref_grads = ref.loss_and_grads(
        params, batch["tokens"], batch["labels"], TINY_MOE)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    router = grads["seg0"]["pos1"]["router"]["w"]
    np.testing.assert_allclose(
        router, ref_grads["seg0"]["pos1"]["router"]["w"],
        rtol=1e-3, atol=1e-6)
