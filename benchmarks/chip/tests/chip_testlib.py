"""Helpers for the benchmark's CPU tests: a copy of the benchmark's
files with tiny cells added by files alone, and a run of one of them
with the look for a TPU skipped."""

from __future__ import annotations

import contextlib
import io
import json
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]

TINY_MOE = {
    "source": "test", "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 1,
    "num_local_experts": 4, "num_experts_per_tok": 2,
    "rms_norm_eps": 1e-5, "rope_theta": 1e6, "sliding_window": None,
    "vocab_size": 256, "reduced": [], "arch": "mixtral-8x7b",
    "replace": {"d_model": 64, "d_ff": 128, "num_heads": 4,
                "num_kv_heads": 2, "head_dim": 16, "vocab_size": 256,
                "num_experts": 4, "top_k": 2, "capacity_factor": 2.0,
                "window": None, "num_layers": 1,
                "segments": [[["attn_local", "moe"], 1]]},
    "precision": "bf16", "control_precision": "fp8",
    "reference": "decoder", "costs": "decoder"}

TINY_DENSE = {
    "source": "test", "hidden_act": "gelu_pytorch_tanh",
    "hidden_size": 64, "intermediate_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_hidden_layers": 1, "rms_norm_eps": 1e-5, "rope_theta": 1e5,
    "sliding_window": None, "use_bias": True, "vocab_size": 256,
    "reduced": [], "arch": "starcoder2-15b",
    "replace": {"d_model": 64, "d_ff": 256, "num_heads": 4,
                "num_kv_heads": 2, "head_dim": 16, "vocab_size": 256,
                "num_layers": 1, "segments": [[["attn", "mlp"], 1]]},
    "precision": "bf16", "control_precision": "fp8",
    "reference": "decoder", "costs": "decoder"}

# Limits of the tiny cells, from CPU runs.  Serving, seeds 1-12, one
# second: the program (bf16) read mean_logit_gap at most 0.0082 (one
# router flip, gap 1.14, among 141 tokens) and its fp8 control at least
# 0.016; clear_max_gap, the widest gap where the router margin is at
# least 0.01, at most 0.0222 against the control's 0.349 at least.  Training, seeds
# 1-6: loss_gap at most 2.7e-4 against the control's 1.0e-3 at least;
# grad_norm_gap 3.5e-3 against 8.3e-3; update_norm_gap does not separate
# the control (both 0.001 to 0.04) and is held only against a state left
# unchanged, which reads 1.
TINY_CHAT = {
    "config": "tiny-moe", "chips": 1, "kind": "serve", "why": "test",
    "slots": 4, "max_ctx": 96,
    "mix": {"rate": 8.0, "prompt_median": 12, "prompt_sigma": 0.5,
            "prompt_min": 4, "prompt_max": 24, "prompt_round": 8,
            "out_median": 16, "out_sigma": 0.5, "out_min": 4,
            "out_max": 40},
    "check_requests": 12,
    "clear_margin": 0.01,
    "limits": {"mean_logit_gap": 0.012, "clear_max_gap": 0.1}}

TINY_TRAIN = {
    "config": "tiny-dense", "chips": 1, "kind": "train", "why": "test",
    "batch": 4, "seq_len": 32,
    "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                  "weight_decay": 0.1, "clip_norm": 1.0,
                  "warmup_steps": 100, "total_steps": 10000,
                  "min_lr_ratio": 0.1},
    "limits": {"loss_gap": 5e-4, "grad_norm_gap": 6e-3,
               "update_norm_gap": 0.3}}


def tiny_tree(tmp: Path) -> tuple[Path, Path]:
    """A checkout-like root with the benchmark's files and tiny cells
    added as files: returns (root, benchmark directory)."""
    here = tmp / "benchmarks" / "chip"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    for name, body in (("tiny-moe", TINY_MOE), ("tiny-dense", TINY_DENSE)):
        (here / "configs" / f"{name}.json").write_text(json.dumps(body))
    cells = {"tiny-moe.chat": TINY_CHAT, "tiny-dense.train": TINY_TRAIN}
    for name, body in cells.items():
        (here / "workloads" / f"{name}.json").write_text(json.dumps(body))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name, body in cells.items():
        bench["workloads"].append(
            {"name": name, "config": body["config"],
             "traffic": name.split(".", 1)[1], "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            base = m["workloads"][0].split(".")[0]
            kind = "tiny-moe.chat" if base == "mixtral-8x7b" \
                else "tiny-dense.train"
            m["workloads"].append(kind)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp, here


def run_tiny(root: Path, here: Path, cell: str, *extra: str,
             seed: int = 7, seconds: float = 1.0) -> tuple[dict, str]:
    """One run of a tiny cell on the CPU; returns (result, stdout)."""
    from benchmarks.chip import harness

    out = io.StringIO()
    with contextlib.redirect_stdout(out), \
            contextlib.redirect_stderr(io.StringIO()):
        result = harness.main(
            ["--workload", cell, "--seed", str(seed),
             "--seconds", str(seconds), *extra],
            here=here, root=root, require_tpu=False)
    return result, out.getvalue()
