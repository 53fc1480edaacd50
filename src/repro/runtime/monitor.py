"""Step-time telemetry, straggler detection and program spans.

At 1000+ nodes the dominant failure mode short of a crash is a slow
host (thermal throttle, flaky HBM, background daemon). The monitor
keeps a rolling window of per-step wall times, computes robust z-scores
(median/MAD), and flags outliers; launch/train.py logs the flag and a
real deployment wires it to the scheduler's drain-and-replace hook.
Also accounts model FLOPs -> achieved FLOP/s for the live MFU readout.

``span`` marks a stretch of host work: a ``jax.profiler.TraceAnnotation``
(so a profiler trace shows it on the clock of the device's operations)
and a record in a bounded in-memory ring, read by ``recent_spans``.
Recording is always on; a span costs two to three microseconds when no
trace is being taken.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time

import jax

__all__ = ["StepMonitor", "run_header", "span", "recent_spans",
           "SPAN_RING"]

SPAN_RING = 2 ** 17    # spans kept, the newest

# (name, start_ns, end_ns, span_id, parent_id, attrs), monotonic_ns
_spans: collections.deque = collections.deque(maxlen=SPAN_RING)
_span_ids = itertools.count(1)
_open = threading.local()   # per thread: ids of the spans open in it


class _Span:
    """One span; ``attrs`` set inside it reach the ring, not the
    profiler's trace (which takes them on entry)."""

    __slots__ = ("name", "attrs", "id", "parent", "start", "_ann",
                 "_stack")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_Span":
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        self._stack = stack
        self.parent = stack[-1] if stack else None
        self.id = next(_span_ids)
        stack.append(self.id)
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self.start = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> bool:
        end = time.monotonic_ns()
        self._ann.__exit__(*exc)
        self._stack.pop()
        _spans.append((self.name, self.start, end, self.id, self.parent,
                       self.attrs))
        return False


def span(name: str, **attrs) -> _Span:
    """Context manager: the host work inside it is the span ``name``.
    Its parent is the innermost span open in the same thread."""
    return _Span(name, attrs)


def recent_spans() -> list[tuple]:
    """The newest ``SPAN_RING`` finished spans in the order they closed,
    each ``(name, start_ns, end_ns, span_id, parent_id, attrs)`` on
    ``time.monotonic_ns``'s clock; ``parent_id`` is None at the top."""
    return list(_spans)


def run_header(arch: str, *, policy=None, mesh=None) -> str:
    """One attributable run-header line: arch, mesh topology, and the
    per-family routed impl.  Launchers print it and bench writers embed
    the same mesh string, so a sharded row in a BENCH_*.json is
    traceable to the exact (mesh, route) that produced it."""
    parts = [f"run: {arch}"]
    if mesh is not None and not mesh.is_identity:
        parts.append(f"mesh {mesh.describe()} ({mesh.size} devices)")
    else:
        parts.append("mesh none (single-device)")
    if policy is not None:
        from repro.core.ops import default_interpret, registry
        routed = " ".join(
            f"{fam}={policy.impl_for(fam)}"
            for fam in sorted(registry.families()))
        parts.append(routed)
        # Off the TPU every Pallas kernel silently runs interpreted;
        # say so, so a run that missed the chip cannot pass for one.
        interpret = (default_interpret() if policy.interpret is None
                     else policy.interpret)
        mode = "interpret" if interpret else "compiled"
        parts.append(f"{jax.default_backend()} (pallas {mode})")
    return " | ".join(parts)


def _median(sorted_xs) -> float:
    """Two-point median of an already-sorted sequence.  ``xs[n // 2]``
    is biased high for even lengths (it picks the upper of the middle
    pair), which inflated both the median and — worse — the MAD scale
    the straggler z-score divides by."""
    n = len(sorted_xs)
    mid = n // 2
    if n % 2:
        return sorted_xs[mid]
    return 0.5 * (sorted_xs[mid - 1] + sorted_xs[mid])


@dataclasses.dataclass
class StepStats:
    mean_s: float
    median_s: float
    mad_s: float
    last_s: float
    straggler: bool
    achieved_tflops: float


class StepMonitor:
    """Rolling robust step-time stats + optional metrics publishing.

    ``metrics`` is duck-typed (any object with ``histogram`` /
    ``gauge`` / ``counter`` get-or-create methods, e.g.
    ``repro.serve.metrics.MetricsRegistry``): every ``stop()`` then
    also observes ``<name>_time_seconds``, sets
    ``<name>_achieved_tflops`` and counts ``<name>_straggler_flags`` —
    the serve stack's scrape surface grows out of the same window the
    straggler detector already keeps.
    """

    def __init__(self, window: int = 50, z_threshold: float = 4.0,
                 model_flops_per_step: float = 0.0,
                 metrics=None, name: str = "step"):
        self.times: collections.deque = collections.deque(maxlen=window)
        self.z = z_threshold
        self.flops = model_flops_per_step
        self._t0: float | None = None
        self._metrics = metrics
        self._name = name

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self) -> StepStats:
        assert self._t0 is not None, "start() not called"
        dt = time.perf_counter() - self._t0
        self._t0 = None
        return self.observe(dt)

    def observe(self, dt: float) -> StepStats:
        """Fold one step duration (seconds) into the window; ``stop()``
        routes through here, and externally-timed paths (the serve
        engine's jit'd tick) call it directly."""
        self.times.append(dt)
        ts = sorted(self.times)
        n = len(ts)
        med = _median(ts)
        mad = _median(sorted(abs(t - med) for t in ts))
        straggler = n >= 10 and mad > 0 and (dt - med) / (1.4826 * mad) > self.z
        stats = StepStats(
            mean_s=sum(ts) / n, median_s=med, mad_s=mad, last_s=dt,
            straggler=straggler,
            achieved_tflops=self.flops / dt / 1e12 if self.flops else 0.0)
        if self._metrics is not None:
            self._metrics.histogram(
                f"{self._name}_time_seconds",
                "per-step wall time").observe(dt)
            if self.flops:
                self._metrics.gauge(
                    f"{self._name}_achieved_tflops",
                    "model FLOPs / step wall time").set(
                        stats.achieved_tflops)
            if straggler:
                self._metrics.counter(
                    f"{self._name}_straggler_flags",
                    "robust-z outlier steps").inc()
        return stats
