"""The program's own spans (``repro.runtime.monitor.span``) in the traced
part of a window, and the time the chip idled inside them.

The program stamps a span on ``time.monotonic_ns``; the trace has its own
clock.  The harness stamps ``tracer.t_on`` on the monotonic clock just
after the ``bench.window`` span opens, whose start on the trace's clock
is ``trace.window[0]``, so one offset maps the first clock onto the
second.  The mapping is checked on every run against the harness's own
spans, which the trace holds: each program span in ``CLOCK_CHECK`` must
lie inside a harness span that wraps it, within ``SLACK_NS``.  Where
fewer than ``AGREE`` of them do, the idle readers read nothing.

A program that records no spans gives every reader nothing to read.
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip.readings import in_trace
from benchmarks.chip.trace import union_ns

__all__ = ["recorded", "traced", "on_trace_clock", "clock_agrees",
           "idle_ms", "mean_idle_ms", "CLOCK_CHECK", "SLACK_NS", "AGREE"]

# per program layer (the span name's prefix): a program span, and the
# harness span that wraps each call of it
CLOCK_CHECK = {"engine": ("engine.tick", "bench.tick"),
               "train": ("train.step", "bench.step")}
SLACK_NS = 50_000
AGREE = 0.99


def recorded() -> list[tuple]:
    """Every span the program recorded, ``(name, start_ns, end_ns, id,
    parent, attrs)`` on the monotonic clock; empty where the program
    records none."""
    try:
        from repro.runtime.monitor import recent_spans
    except ImportError:
        return []
    return recent_spans()


def traced(run, name: str) -> list[tuple]:
    """The spans ``name`` that lie in the traced part of the window."""
    return [sp for sp in recorded() if sp[0] == name and
            in_trace(run.runner, sp[1] * 1e-9, sp[2] * 1e-9)]


def on_trace_clock(run, spans) -> np.ndarray:
    """``(n, 2)`` starts and ends of ``spans`` in the trace's ns."""
    offset = run.trace.window[0] - run.runner.tracer.t_on * 1e9
    return np.array([(sp[1], sp[2]) for sp in spans],
                    float).reshape(-1, 2) + offset


def clock_agrees(run, inner: str, outer: str) -> bool:
    """Whether at least ``AGREE`` of the traced spans ``inner``, mapped
    onto the trace's clock, lie inside a harness span ``outer``."""
    mapped = on_trace_clock(run, traced(run, inner))
    host = sorted((s, e) for n, s, e in run.trace.host if n == outer)
    if not len(mapped) or not host:
        return False
    starts, ends = np.array(host).T
    # the last harness span to start by the inner span's start
    k = np.searchsorted(starts, mapped[:, 0] + SLACK_NS, side="right") - 1
    inside = (k >= 0) & (ends[np.maximum(k, 0)] >= mapped[:, 1] - SLACK_NS)
    return bool(inside.mean() >= AGREE)


def idle_ms(run, name: str) -> list[float] | None:
    """Per traced span ``name``: milliseconds inside it in which no
    operation ran on the first device (the union of its operations, as
    ``trace.idle_gaps`` takes it).  None without a device trace, without
    such spans, or where the clocks disagree."""
    tr = run.trace
    if tr is None or not tr.ops:
        return None
    spans = traced(run, name)
    if not spans or not clock_agrees(run, *CLOCK_CHECK[name.split(".")[0]]):
        return None
    # busy pieces, led by an empty one at 0 so that one starts by any t
    pieces = np.array([(0.0, 0.0)] + union_ns(tr.ops[sorted(tr.ops)[0]]))
    starts, ends = pieces[:, 0], pieces[:, 1]
    before = np.concatenate([[0.0], np.cumsum(ends - starts)])

    def busy_until(t):
        k = np.searchsorted(starts, t, side="right")
        return before[k] - np.maximum(ends[k - 1] - t, 0.0)

    s, e = on_trace_clock(run, spans).T
    idle = (e - s) - (busy_until(e) - busy_until(s))
    return list(idle * 1e-6)


def mean_idle_ms(run, name: str) -> float | None:
    idle = idle_ms(run, name)
    return None if idle is None else float(np.mean(idle))
