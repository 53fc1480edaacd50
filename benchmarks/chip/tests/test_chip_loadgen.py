"""The traffic generator, and the command's refusal to run without a
TPU or without the program."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
from chip_testlib import HERE, ROOT

from benchmarks.chip import loadgen

MIX = loadgen.Mix.from_dict({
    "rate": 6.0, "prompt_median": 384, "prompt_sigma": 0.8,
    "prompt_min": 64, "prompt_max": 2048, "prompt_round": 256,
    "out_median": 192, "out_sigma": 0.7, "out_min": 16, "out_max": 768})


def _key(arrivals):
    return [(a.due_s, a.prompt.tolist(), a.max_new_tokens) for a in arrivals]


def test_same_seed_same_schedule():
    a = loadgen.schedule(MIX, 30, 2**33 + 5, 32000)
    b = loadgen.schedule(MIX, 30, 2**33 + 5, 32000)
    assert _key(a) == _key(b)
    assert _key(a) != _key(loadgen.schedule(MIX, 30, 6, 32000))


def test_seeds_share_sizes_and_gaps_in_another_order():
    a = loadgen.schedule(MIX, 30, 1, 32000)
    b = loadgen.schedule(MIX, 30, 2, 32000)
    assert len(a) == len(b) == round(MIX.rate * 30)
    for f in (lambda x: len(x.prompt), lambda x: x.max_new_tokens,
              lambda x: x.due_s):
        assert list(map(f, a)) == list(map(f, b))
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in b]
    assert all(0 <= x.due_s < 30 for x in a + b)


def test_quantised_lengths_fall_in_their_range():
    arr = loadgen.schedule(MIX, 60, 3, 32000)
    lens = np.array([len(a.prompt) for a in arr])
    outs = np.array([a.max_new_tokens for a in arr])
    assert (lens % 256 == 0).all() and lens.min() >= 256
    assert lens.max() <= 2048
    assert outs.min() >= 16 and outs.max() <= 768
    ids = np.concatenate([a.prompt for a in arr])
    assert ids.min() >= 2 and ids.max() < 32000


def test_generator_that_falls_behind_is_reported():
    due = [0.0, 0.1, 0.2, 0.3]
    on_time = loadgen.lateness(due, due)
    assert on_time["max_ms"] == 0.0
    late = loadgen.lateness(due, [0.0, 0.1, 0.45, 0.3])
    assert late["max_ms"] > 249.0 and late["p95_ms"] > 0.0


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "mixtral-8x7b.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def _no_result(proc):
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    try:
        json.loads(last)
    except ValueError:
        return True
    return False


def test_command_exits_nonzero_without_a_tpu():
    proc = _run(ROOT, {})
    assert proc.returncode != 0 and _no_result(proc)
    assert "no TPU" in proc.stderr


def test_command_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, {})
    assert proc.returncode != 0 and _no_result(proc)


def test_order_is_balanced_in_blocks():
    n, block = 326, loadgen.BLOCK
    full = n // block
    order = loadgen._balanced(np.arange(n), loadgen.rng_for(9, 1))
    assert sorted(order) == list(range(n))
    assert list(order) != sorted(order)
    for start in range(0, full * block, block):
        ranks = np.sort(order[start:start + block])
        k = np.arange(block)
        assert ((ranks >= k * full) & (ranks < (k + 1) * (full + 1))).all()
