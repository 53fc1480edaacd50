"""The harness's host spans and the part of a window that is traced.

Spans are ``jax.profiler.TraceAnnotation`` names starting with
``bench.``; they cost about a microsecond when no trace is being taken,
so every run records them, and a ``--trace 1`` run finds them on the
trace's host plane (``trace.py``).
"""

from __future__ import annotations

import time

import jax

__all__ = ["span", "wrap", "Tracer", "TRACE_S"]

TRACE_S = 10.0       # the traced part of a --trace 1 window, at most


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


def wrap(fn, name: str):
    """``fn`` inside the span ``name``."""
    def wrapped(*a, **k):
        with span(name):
            return fn(*a, **k)
    return wrapped


class Tracer:
    """Starts the profiler for the last ``TRACE_S`` of a window and stops
    it at the window's close, inside the host span ``bench.window``.
    Starting and stopping the profiler stall the host; the traced part
    begins after the first stall and the second falls after the close,
    so per-layer readings taken over the traced part see neither.
    ``poll`` is called from the window's loop with the seconds since it
    opened."""

    def __init__(self, trace_dir: str | None, seconds: float):
        self.dir = trace_dir
        self.start = max(0.0, seconds - TRACE_S)
        self.stop_at = seconds
        self.state = "before" if trace_dir else "done"
        self._span = None
        self.t_on = self.t_off = None   # host monotonic seconds

    def poll(self, elapsed: float) -> None:
        if self.state == "before" and elapsed >= self.start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._span = span("bench.window")
            self._span.__enter__()
            self.t_on = time.monotonic()
            self.state = "on"
        elif self.state == "on" and elapsed >= self.stop_at:
            self.close()

    def close(self) -> None:
        if self.state == "on":
            self.t_off = time.monotonic()
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.state = "done"
