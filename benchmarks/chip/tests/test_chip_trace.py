"""The trace reduction on a small trace recorded on one TPU v5 lite:
five rounds of two jitted programs (``tick`` and ``prefill``) inside
the harness's host spans, with a 2 ms ``bench.wait`` after each round
(``data/small.xplane.pb``, 29,847 bytes)."""

from pathlib import Path

import pytest

from benchmarks.chip import trace

SMALL = Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return trace.load(str(SMALL))


def test_window_is_the_harness_span(tr):
    assert list(tr.ops) == ["/device:TPU:0"]
    assert tr.window_s == pytest.approx(0.020734207)


def test_busy_union_and_idle_share(tr):
    busy = trace.busy_s(tr)
    assert 0 < busy < tr.window_s
    # the programs are tiny against the waits: the chip idles >99%
    assert 1 - busy / tr.window_s > 0.99


def test_program_times_by_stable_name(tr):
    times = trace.program_times(tr)
    assert set(times) == {"jit_tick", "jit_prefill"}
    assert times["jit_prefill"][0] == 5
    # the first tick ran on the device before the host opened the span
    assert times["jit_tick"][0] == 4
    assert all(t > 0 for _, t in times.values())


def test_top_ops_are_named_and_ranked(tr):
    ops = trace.top_ops(tr)
    assert 0 < len(ops) <= 10
    assert all(" = " not in name for name, _ in ops)
    secs = [t for _, t in ops]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) == pytest.approx(trace.busy_s(tr), rel=0.05)


def test_idle_gaps_are_labelled_by_host_spans(tr):
    gaps = trace.idle_gaps(tr)
    assert len(gaps) == 10
    assert gaps[0][0] == "bench.wait" and gaps[0][1] > 0.002
    assert {name for name, _ in gaps} <= {
        "bench.wait", "bench.step", "bench.tick", "bench.admit", "host"}


def test_union_merges_overlaps():
    ivs = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 40, 41)]
    assert trace.union_ns(ivs) == [(0, 20), (30, 41)]


def test_stable_names():
    assert trace.stable_name("jit_tick(9316458401576507835)") == "jit_tick"
    assert trace.op_name("%fusion.42 = f32[8] fusion(%a)") == "fusion.42"
