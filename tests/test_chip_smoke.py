"""``chip_smoke.py`` on the CPU, so the script cannot rot between chip runs.

Its phase functions run here at ``get_smoke("gemma3-1b")`` size with the
Pallas kernels in interpret mode; only the platform check is skipped,
in the test.  The script itself, run as a program, must refuse a machine
with no TPU and a directory without the rest of the repository.
"""

from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config, get_smoke
from repro.models import api
from repro.runtime.compile_cache import REPO_CACHE_DIR

ROOT = Path(__file__).resolve().parents[1]
SIZES = dict(batch=2, max_ctx=64, prompt_len=8, new_tokens=4, requests=3,
             stagger=1, train_batch=2, train_seq=32, train_steps=3)


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod        # dataclasses resolve the module
    spec.loader.exec_module(mod)
    return mod


def _run(args, *, cwd, env_extra, timeout=300):
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.environ.get("HOME", str(cwd)), "JAX_PLATFORMS": "cpu",
           **env_extra}
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_serve_phases_xla_and_pallas_paged_agree(cs):
    cfg = get_smoke("gemma3-1b")
    sizes = cs.Sizes(**SIZES)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    prompts = cs.make_prompts(cfg, sizes, seed=0)
    probe = int(prompts[1][0])
    xla = cs.serve_phase(cfg, params, prompts, cs.serve_policy(cfg),
                         sizes=sizes, route="serve_xla", kv_layout="dense",
                         probe_token=probe)
    pallas = cs.serve_phase(
        cfg, params, prompts,
        cs.serve_policy(cfg, cs.PALLAS_BACKENDS, kv_layout="paged"),
        sizes=sizes, route="serve_pallas", kv_layout="paged",
        probe_token=probe)
    for run in (xla, pallas):
        assert [len(o) for o in run.outputs] == [sizes.new_tokens] * 3
        assert run.tokens == sizes.requests * sizes.new_tokens
        assert run.prefill_logits.shape == (cfg.vocab_size,)
    deltas = cs.compare_serve(xla, pallas)
    assert max(deltas.values()) <= cs.LOGIT_RTOL


def test_train_phase_loss_falls(cs):
    sizes = cs.Sizes(**SIZES)
    run = cs.train_phase(cs.train_config(get_smoke("gemma3-1b")),
                         sizes=sizes, seed=0)
    assert len(run.losses) == sizes.train_steps
    assert np.isfinite(run.losses).all() and run.losses[-1] < run.losses[0]
    assert cs.param_devices(run.params) == {jax.devices()[0]}


def test_train_config_cuts_depth_to_one_period(cs):
    full = get_config("gemma3-1b")
    cut = cs.train_config(full)
    pattern = cut.segments[0].pattern
    assert cut.num_layers == 6 and len(cut.segments) == 1
    assert pattern.count("attn_local") == 5 and pattern.count("attn") == 1
    assert (cut.d_model, cut.d_ff, cut.vocab_size) == (
        full.d_model, full.d_ff, full.vocab_size)


def test_main_exits_nonzero_without_tpu(tmp_path):
    r = _run(["chip_smoke.py"], cwd=ROOT,
             env_extra={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_script_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    r = _run(["chip_smoke.py"], cwd=tmp_path, env_extra={})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "repo"])
def test_compile_cache_directory(tmp_path, from_env):
    """``$JAX_COMPILATION_CACHE_DIR`` when set (and compiled programs
    land there), else the fixed ``<repo>/.jax_cache``."""
    code = ("import jax\n"
            "from repro.runtime.compile_cache import enable_compile_cache\n"
            "path = enable_compile_cache()\n"
            "assert jax.config.jax_compilation_cache_dir == path\n"
            "print(path)\n")
    env = {"PYTHONPATH": str(ROOT / "src")}
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
        code += ("jax.config.update("
                 "'jax_persistent_cache_min_compile_time_secs', 0)\n"
                 "jax.jit(lambda x: x + 1)(1.0).block_until_ready()\n")
    r = _run(["-c", code], cwd=tmp_path, env_extra=env)
    assert r.returncode == 0, r.stderr[-2000:]
    if from_env:
        assert r.stdout.split() == [str(tmp_path / "cc")]
        assert os.listdir(tmp_path / "cc")
    else:
        assert r.stdout.split() == [str(REPO_CACHE_DIR)]
        assert REPO_CACHE_DIR == ROOT / ".jax_cache"
