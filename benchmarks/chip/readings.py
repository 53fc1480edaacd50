"""Shared arithmetic of the metric readers in ``metrics/``."""

from __future__ import annotations

import numpy as np

__all__ = ["p95", "in_trace", "serve_prefills", "serve_ticks",
           "tick_device_s", "TICK", "PREFILL"]

TICK = "jit_tick"          # the engine tick's jitted program
PREFILL = "jit_prefill"    # the engine's prefill program


def p95(values) -> float | None:
    v = np.asarray(list(values), float)
    return float(np.percentile(v, 95)) if v.size else None


def in_trace(runner, start: float, end: float) -> bool:
    """Whether the host interval ``[start, end]`` lies in the traced
    part of the window."""
    t = getattr(runner, "tracer", None)
    return bool(t and t.t_on is not None and t.t_off is not None
                and start >= t.t_on and end <= t.t_off)


def serve_prefills(run) -> list[int]:
    """Prompt lengths of the requests admitted in the traced part."""
    d = run.runner
    return [r.prompt_len for r in d.recs
            if r.req is not None and r.req.t_admit is not None
            and in_trace(d, r.req.t_admit, r.req.t_admit)]


def serve_ticks(run) -> list[tuple]:
    """Engine steps in the traced part that ticked at least one slot."""
    return [t for t in run.runner.ticks
            if t[2] > 0 and in_trace(run.runner, t[0], t[1])]


def tick_device_s(run) -> float | None:
    """Mean device seconds of one engine-tick program in the trace."""
    if run.trace is None:
        return None
    from benchmarks.chip.trace import program_times
    n, total = program_times(run.trace).get(TICK, (0, 0.0))
    return total / n if n else None
