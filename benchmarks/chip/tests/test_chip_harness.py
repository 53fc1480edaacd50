"""Whole runs of tiny cells on the CPU, with the look for a TPU skipped.

The cells are added to a copy of the benchmark as files alone (a
configuration, a workload and BENCHMARK.json entries), which is how a
later change adds one.  Each run goes through set-up, the window, the
check against the reference and the result line.  Then the control
(the configuration's lower precision) and each fault a cell can have,
planted in the program underneath the harness, must come out as not
correct.
"""

import jax
import pytest
from chip_testlib import run_tiny, tiny_tree

import repro.launch.train as train_mod
from repro.runtime import serve_step


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield tiny_tree(tmp_path_factory.mktemp("bench"))
    from jax.experimental.compilation_cache import compilation_cache
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def _ok(result):
    return result["correct"] and result["failed"] == 0


def test_serve_cell_added_by_files_runs_and_is_correct(tree):
    result, out = run_tiny(*tree, "tiny-moe.chat")
    assert _ok(result), result
    assert set(result["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    assert result["device"]["platform"] == "cpu"
    assert "compiles_in_window lowered=0 compiled=0" in out
    assert "generator_late_ms" in out and "longest_steps" in out
    assert list(result)[-1] == "checks"


def test_serve_traced_run_reports_per_layer_metrics(tree):
    result, _ = run_tiny(*tree, "tiny-moe.chat", "--trace", "1")
    assert _ok(result), result
    assert {"engine.queue_wait_p95_ms", "engine.slot_occupancy"} <= set(
        result["metrics"])
    assert "window_s" in result["device"] and "breakdown" in result


def test_train_cell_added_by_files_runs_and_is_correct(tree):
    result, out = run_tiny(*tree, "tiny-dense.train")
    assert _ok(result), result
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert "compiles_in_window lowered=0 compiled=0" in out


@pytest.mark.parametrize("cell", ["tiny-moe.chat", "tiny-dense.train"])
def test_control_precision_is_not_correct(tree, cell):
    result, _ = run_tiny(*tree, cell, "--control")
    assert not result["correct"], result["checks"]


def test_served_token_altered_is_not_correct(tree, monkeypatch):
    real = serve_step.make_engine_tick

    def altered(cfg, policy, **kw):
        tick = real(cfg, policy, **kw)

        def wrong(params, cache, last_tok, pos, active, remaining):
            out = tick(params, cache, last_tok, pos, active, remaining)
            nxt = out[1].at[0].set((out[1][0] + 1) % cfg.vocab_size)
            return (out[0], nxt, *out[2:])

        return wrong

    monkeypatch.setattr(serve_step, "make_engine_tick", altered)
    result, _ = run_tiny(*tree, "tiny-moe.chat", seed=8)
    assert not result["correct"], result["checks"]


def _broken_train(monkeypatch, fault):
    real = train_mod.make_train_step

    def make(cfg, opt_cfg, policy, **kw):
        step = real(cfg, opt_cfg, policy, **kw)

        def broken(params, opt, batch):
            if fault == "half_batch":
                half = batch["tokens"].shape[0] // 2
                return step(params, opt, {k: v[:half]
                                          for k, v in batch.items()})
            new_params, new_opt, m = step(params, opt, batch)
            return params, opt, m

        return broken

    monkeypatch.setattr(train_mod, "make_train_step", make)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_fault_is_not_correct(tree, monkeypatch, fault):
    _broken_train(monkeypatch, fault)
    result, _ = run_tiny(*tree, "tiny-dense.train", seed=9)
    assert not result["correct"], result["checks"]
