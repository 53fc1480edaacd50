"""The DeepSeek-V2 configuration's benchmark files on the CPU: the cost
model's FLOPs against the dot FLOPs ``repro.analysis.hlo_cost`` counts
in the program's compiled forward pass, and a tiny latent-attention
serving cell added by files alone, run through ``harness.main`` with the
look for a TPU skipped; its control precision and an altered served
token must come out as not correct."""

import json

import jax
import jax.numpy as jnp
import pytest
from chip_testlib import HERE, run_tiny, tiny_tree

from benchmarks.chip import harness
from repro.analysis.hlo_cost import analyze_hlo
from repro.configs.base import execution_policy_for
from repro.models import transformer as T
from repro.runtime import serve_step

costs = harness.load_module(HERE / "costs" / "mla.py")

# The smoke preset's widths under the published keys: one dense layer,
# two expert layers holding 4 of 8 routed experts, top-2, two shared.
TINY_MLA = {
    "source": "test", "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 64,
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
    "kv_lora_rank": 32, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "n_routed_experts": 8, "num_local_experts": 4, "num_experts_per_tok": 2,
    "n_shared_experts": 2, "routed_scaling_factor": 1,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "vocab_size": 256, "reduced": [], "arch": "deepseek-v2-lite",
    "replace": {"d_model": 64, "num_layers": 3, "vocab_size": 256,
                "num_heads": 2, "num_kv_heads": 2, "head_dim": 32,
                "kv_lora_rank": 32, "qk_rope_dim": 16, "v_head_dim": 32,
                "d_ff": 128, "moe_d_ff": 64, "shared_d_ff": 128,
                "num_experts": 4, "num_routed_experts": 8, "top_k": 2,
                "capacity_factor": 2.0,
                "segments": [[["mla", "mlp"], 1], [["mla", "moe"], 2]]},
    "precision": "bf16", "control_precision": "fp8",
    "reference": "mla", "costs": "mla"}

# Limits of the tiny cell, from CPU runs of seeds 1-8, one second: the
# program (bf16) read mean_logit_gap at most 0.0023 and its fp8 control
# at least 0.068; clear_max_gap at most 0.060 against the control's
# 0.75 at least.
TINY_MLA_CHAT = {
    "config": "tiny-mla", "chips": 1, "kind": "serve", "why": "test",
    "slots": 4, "max_ctx": 96,
    "mix": {"rate": 8.0, "prompt_median": 12, "prompt_sigma": 0.5,
            "prompt_min": 4, "prompt_max": 24, "prompt_round": 8,
            "out_median": 16, "out_sigma": 0.5, "out_min": 4,
            "out_max": 40},
    "check_requests": 8,
    "clear_margin": 0.01,
    "limits": {"mean_logit_gap": 0.012, "clear_max_gap": 0.2}}

B, S = 2, 64


def _model(config):
    cell = harness.Cell(name="t", entry={"config": "t"},
                        traffic={"seq_len": S}, config=config, seed=0,
                        seconds=1, precision="bf16", here=HERE)
    return harness.build_model(cell)


def test_mla_forward_flops_match_compiled_dots():
    """The training forward expands the latent (``wkv_b`` on every
    token), computes every (query, key) pair of its one key chunk, and
    runs every held expert over a capacity of ``capacity_factor * top_k
    * T / n_routed_experts`` rows; the cost model counts the causal
    half and the held share of ``top_k`` experts per token.  Corrected
    for those, they agree."""
    c = TINY_MLA
    cfg = _model(c)
    m = costs.dims(c)
    policy = execution_policy_for(cfg, default="bf16")
    params = serve_step.abstract_params(cfg)
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)

    def fwd(p, t):
        return T.forward(p, t, cfg, policy=policy, mode="train")[0]

    got = analyze_hlo(jax.jit(fwd).lower(params, tokens).compile()
                      .as_text()).flops
    t = B * S
    expert = 2 * 3 * m.d * m.moe_f
    capacity = int(cfg.capacity_factor * m.top_k * t / m.routed)
    square = 2 * m.layers * m.heads * (m.nope + m.rope + m.v) * S * S
    want = (costs.matmul_flops_per_token(c, head=True) * t
            - m.moe_layers * m.assignments * expert * t
            + m.moe_layers * m.held * capacity * expert + B * square)
    assert got == pytest.approx(want, rel=1e-6)


def test_absorbed_decode_counts_the_latent_not_the_heads():
    """A decode key costs the absorbed path's 2 * heads * (2 * rank +
    rope) FLOPs and reads rank + rope values a layer; at the published
    widths that is 34,816 FLOPs and 1,152 bytes."""
    cfg = json.loads((HERE / "configs" / "deepseek-v2-lite.json")
                     .read_text())
    one = costs.decode_tick(cfg, [1001])
    two = costs.decode_tick(cfg, [1002])
    assert two["flops"] - one["flops"] == 6 * 34816
    assert two["bytes"] - one["bytes"] == 6 * 1152


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    root, here = tiny_tree(tmp_path_factory.mktemp("bench"))
    (here / "configs" / "tiny-mla.json").write_text(json.dumps(TINY_MLA))
    (here / "workloads" / "tiny-mla.chat.json").write_text(
        json.dumps(TINY_MLA_CHAT))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-mla.chat",
                               "config": "tiny-mla", "traffic": "chat",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-moe.chat" in m.get("workloads", ()):
            m["workloads"].append("tiny-mla.chat")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    yield root, here
    from jax.experimental.compilation_cache import compilation_cache
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_mla_cell_added_by_files_runs_and_is_correct(tree):
    result, out = run_tiny(*tree, "tiny-mla.chat")
    assert result["correct"] and result["failed"] == 0, result
    assert set(result["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    assert "compiles_in_window lowered=0 compiled=0" in out


def test_mla_control_precision_is_not_correct(tree):
    result, _ = run_tiny(*tree, "tiny-mla.chat", "--control")
    assert not result["correct"], result["checks"]


def test_mla_served_token_altered_is_not_correct(tree, monkeypatch):
    real = serve_step.make_engine_tick

    def altered(cfg, policy, **kw):
        tick = real(cfg, policy, **kw)

        def wrong(params, cache, last_tok, pos, active, remaining):
            out = tick(params, cache, last_tok, pos, active, remaining)
            nxt = out[1].at[0].set((out[1][0] + 1) % cfg.vocab_size)
            return (out[0], nxt, *out[2:])

        return wrong

    monkeypatch.setattr(serve_step, "make_engine_tick", altered)
    result, _ = run_tiny(*tree, "tiny-mla.chat")
    assert not result["correct"], result["checks"]
