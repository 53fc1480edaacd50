"""The readers of the program's spans: on a made-up trace with known
idle pieces, with a clock that disagrees, and in a traced run of a tiny
serving cell on the CPU."""

import types

import jax
import pytest
from chip_testlib import run_tiny, tiny_tree

from benchmarks.chip import harness, program_spans
from benchmarks.chip.trace import Trace
from repro.runtime import monitor

MS = 1_000_000          # ns
T_ON = 5.0              # monotonic seconds at the traced part's start
W0 = 7 * MS             # the same instant on the trace's clock


def _run(host, spans, monkeypatch, t_on=T_ON):
    """A Run over a made-up trace: the device busy 10-20 and 30-50 ms
    after the window opens, and the program's ``spans`` given on the
    trace's clock (ms after the window opens)."""
    ops = [("a", W0 + 10 * MS, W0 + 15 * MS),
           ("b", W0 + 14 * MS, W0 + 20 * MS),
           ("c", W0 + 30 * MS, W0 + 50 * MS)]
    tr = Trace(ops={"/device:TPU:0": ops}, modules={},
               host=[("bench.window", W0, W0 + 100 * MS)] + [
                   (n, W0 + s * MS, W0 + e * MS) for n, s, e in host],
               window=(W0, W0 + 100 * MS))
    base = int(T_ON * 1e9)
    recorded = [(n, base + int(s * MS), base + int(e * MS), i, None, {})
                for i, (n, s, e) in enumerate(spans, 1)]
    monkeypatch.setattr(monitor, "recent_spans", lambda: recorded)
    tracer = types.SimpleNamespace(t_on=t_on, t_off=T_ON + 0.1)
    return harness.Run(cell=None, runner=types.SimpleNamespace(
        tracer=tracer), costs=None, peaks=None, setup_s=0.0, trace=tr)


# the harness's spans wrap the program's as closely as in a real run
HOST = [("bench.step", 4.45, 39.55), ("bench.tick", 5.45, 35.05),
        ("bench.step", 45.45, 59.05), ("bench.tick", 46.45, 58.55)]
SPANS = [("engine.step", 4.5, 39.5), ("engine.tick", 5.5, 35),
         ("engine.sync", 6, 8), ("engine.step", 45.5, 59),
         ("engine.tick", 46.5, 58.5), ("engine.admit", 90, 95),
         ("engine.step", 99, 101)]   # the last leaves the traced part


def test_idle_inside_spans(monkeypatch):
    run = _run(HOST, SPANS, monkeypatch)
    assert program_spans.clock_agrees(run, "engine.tick", "bench.tick")
    # step 4.5-39.5: busy 10-20 and 30-39.5 -> idle 35 - 19.5 = 15.5;
    # step 45.5-59: busy 45.5-50 -> idle 9
    assert program_spans.idle_ms(run, "engine.step") == pytest.approx(
        [15.5, 9.0])
    # tick 5.5-35: idle 29.5 - 15 = 14.5; tick 46.5-58.5: 12 - 3.5 = 8.5
    assert program_spans.mean_idle_ms(run, "engine.tick") == \
        pytest.approx((14.5 + 8.5) / 2)
    assert program_spans.idle_ms(run, "engine.admit") == pytest.approx(
        [5.0])
    reader = harness.load_module(
        harness.HERE / "metrics" / "engine.host_syncs_per_step.py")
    assert reader.read(run) == pytest.approx(0.5)


def test_train_step_is_checked_against_bench_step(monkeypatch):
    run = _run([("bench.step", 0, 25)], [("train.step", 1, 12)],
               monkeypatch)
    assert program_spans.idle_ms(run, "train.step") == pytest.approx(
        [9.0])


@pytest.mark.parametrize("shift_s", [1e-3, -1e-3])
def test_clock_off_by_a_millisecond_reads_nothing(monkeypatch, shift_s):
    run = _run(HOST, SPANS, monkeypatch, t_on=T_ON + shift_s)
    assert not program_spans.clock_agrees(run, "engine.tick", "bench.tick")
    for name in ("engine.step", "engine.tick", "engine.admit"):
        assert program_spans.idle_ms(run, name) is None


def test_a_program_without_spans_reads_nothing(monkeypatch):
    run = _run(HOST, [], monkeypatch)
    monkeypatch.delattr(monitor, "recent_spans")
    assert program_spans.recorded() == []
    assert program_spans.mean_idle_ms(run, "engine.step") is None


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


def _traced(tree, cell, monkeypatch):
    """A traced run of a tiny cell: its result and what the readers
    read."""
    runs = []

    class Kept(harness.Run):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            runs.append(self)

    monkeypatch.setattr(harness, "Run", Kept)
    result, _ = run_tiny(*tree, cell, "--trace", "1")
    assert result["correct"], result["checks"]
    return result, runs[0]


def test_traced_tiny_serve_run_reports_host_syncs(tree, monkeypatch):
    result, run = _traced(tree, "tiny-moe.chat", monkeypatch)
    # every step ticks: three reads a tick, one an admission
    assert result["metrics"]["engine.host_syncs_per_step"]["value"] >= 3
    # the profiler's own clock, mapped by the one offset
    assert program_spans.clock_agrees(run, "engine.tick", "bench.tick")


def test_traced_tiny_train_run_maps_the_clock(tree, monkeypatch):
    _, run = _traced(tree, "tiny-dense.train", monkeypatch)
    assert program_spans.traced(run, "train.step")
    assert program_spans.clock_agrees(run, "train.step", "bench.step")
