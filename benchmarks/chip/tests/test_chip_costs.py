"""The benchmark's FLOP arithmetic against the dot FLOPs that
``repro.analysis.hlo_cost`` counts in the program's compiled forward
pass, on the tiny presets; and the peaks table's refusal of an unknown
device."""

import jax
import jax.numpy as jnp
import pytest
from chip_testlib import HERE, TINY_DENSE, TINY_MOE

from benchmarks.chip import harness, peaks
from repro.analysis.hlo_cost import analyze_hlo
from repro.configs.base import execution_policy_for
from repro.models import transformer as T
from repro.runtime import serve_step

costs = harness.load_module(HERE / "costs" / "decoder.py")

B, S = 2, 64


def _model(config):
    cell = harness.Cell(name="t", entry={"config": "t"},
                        traffic={"seq_len": S}, config=config, seed=0,
                        seconds=1, precision="bf16", here=HERE)
    return harness.build_model(cell)


def _hlo_flops(cfg):
    policy = execution_policy_for(cfg, default="bf16")
    params = serve_step.abstract_params(cfg)
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)

    def fwd(p, t):
        return T.forward(p, t, cfg, policy=policy, mode="train")[0]

    return analyze_hlo(jax.jit(fwd).lower(params, tokens).compile()
                       .as_text()).flops


def _full_square(c):
    """The program's attention computes every (query, key) pair of its
    one key chunk; the benchmark counts only the causal half."""
    m = costs.dims(c)
    return 4 * m.layers * m.heads * m.head_dim * S * S


def test_dense_forward_flops_match_compiled_dots():
    c = TINY_DENSE
    want = B * (costs.matmul_flops_per_token(c, head=True) * S
                + _full_square(c))
    got = _hlo_flops(_model(c))
    assert got == pytest.approx(want, rel=1e-6)


def test_moe_forward_flops_match_compiled_dots():
    """The program's capacity dispatch runs every expert over a capacity
    of ``capacity_factor * top_k * T / E`` rows; the benchmark counts
    ``top_k`` experts per token.  Corrected for that, they agree."""
    c = TINY_MOE
    cfg = _model(c)
    m = costs.dims(c)
    t = B * S
    ffn = 2 * 3 * m.d * m.f
    capacity = int(cfg.capacity_factor * m.top_k * t / m.experts)
    want = (costs.matmul_flops_per_token(c, head=True) * t
            - m.layers * m.top_k * ffn * t
            + m.layers * m.experts * capacity * ffn
            + B * _full_square(c))
    assert _hlo_flops(cfg) == pytest.approx(want, rel=1e-6)


def test_causal_train_step_is_three_forwards():
    fwd = costs.matmul_flops_per_token(TINY_DENSE, head=True) * S
    m = costs.dims(TINY_DENSE)
    attn = 4 * m.layers * m.heads * m.head_dim * S * (S + 1) // 2
    assert costs.train_step(TINY_DENSE, B, S)["flops"] == 3 * B * (fwd + attn)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v99 imaginary")
    assert peaks.peaks_for("TPU v5 lite").bf16_flops == 197e12
