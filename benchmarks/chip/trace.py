"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

``load`` reads the file with ``jax.profiler.ProfileData`` alone and
keeps three kinds of interval, each ``(name, start_ns, end_ns)`` on the
trace's one clock:

* device operations: the ``XLA Ops`` line of every ``/device:`` plane;
* device programs: the ``XLA Modules`` line of those planes, one event
  per run of a jitted program, named ``jit_<fn>(<id>)``;
* host spans: events of the ``/host:CPU`` plane whose name starts with
  ``bench.`` (the harness's own ``TraceAnnotation`` spans).

The traced window is the host span ``bench.window``; everything is
clipped to it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

__all__ = ["Trace", "load", "find_xplane", "union_ns", "busy_s",
           "program_times", "top_ops", "idle_gaps", "stable_name", "op_name"]

WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    ops: dict[str, list[tuple[str, float, float]]]       # per device
    modules: dict[str, list[tuple[str, float, float]]]   # per device
    host: list[tuple[str, float, float]]
    window: tuple[float, float]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def op_name(name: str) -> str:
    """``%fusion.42 = f32[...] fusion(...)`` -> ``fusion.42``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict = {}
    modules: dict = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [(op_name(n), s, e)
                                       for n, s, e in _events(line)]
                elif line.name == "XLA Modules":
                    modules[plane.name] = list(_events(line))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend(ev for ev in _events(line)
                            if ev[0].startswith("bench."))
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if windows:
        window = windows[0]
    else:  # no window span: the extent of everything on the devices
        spans = [iv for evs in ops.values() for iv in evs]
        window = (min(s for _, s, _ in spans), max(e for _, _, e in spans))
    return Trace(ops=ops, modules=modules, host=host, window=window)


def _clip(intervals, window):
    lo, hi = window
    for name, s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, s, e


def union_ns(intervals) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` pieces covered by any interval."""
    merged: list[list[float]] = []
    for _, s, e in sorted(intervals, key=lambda iv: iv[1]):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_s(tr: Trace) -> float | None:
    """Seconds in which some operation ran, averaged over the devices
    (``None`` where the trace holds no device)."""
    if not tr.ops:
        return None
    total = 0.0
    for evs in tr.ops.values():
        total += sum(e - s for s, e in union_ns(_clip(evs, tr.window)))
    return total / len(tr.ops) * 1e-9


_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)+$")


def stable_name(name: str) -> str:
    """``jit_tick(123)`` -> ``jit_tick``; ``fusion.12`` stays as it is
    only up to its numeric suffix: ``fusion``."""
    return _SUFFIX.sub("", name)


def program_times(tr: Trace) -> dict[str, tuple[int, float]]:
    """Per jitted program (stable name): runs started in the window and
    their device seconds, summed over devices."""
    out: dict[str, list] = {}
    lo, hi = tr.window
    for evs in tr.modules.values():
        for name, s, e in evs:
            if lo <= s < hi:
                rec = out.setdefault(stable_name(name), [0, 0.0])
                rec[0] += 1
                rec[1] += (e - s) * 1e-9
    return {k: (n, t) for k, (n, t) in out.items()}


def top_ops(tr: Trace, n: int = 10) -> list[list]:
    """The ``n`` device operations that took most time in the window,
    as ``[name, seconds]`` averaged over devices."""
    agg: dict[str, float] = {}
    for evs in tr.ops.values():
        for name, s, e in _clip(evs, tr.window):
            agg[name] = agg.get(name, 0.0) + (e - s) * 1e-9
    k = max(len(tr.ops), 1)
    ranked = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[name, t / k] for name, t in ranked]


def idle_gaps(tr: Trace, n: int = 10) -> list[list]:
    """The ``n`` longest gaps in which no device operation ran, each
    ``[label, seconds]``.  The label is the innermost harness span that
    covers most of the gap (``host`` where none does); gaps are taken
    on the first device."""
    if not tr.ops:
        return []
    dev = sorted(tr.ops)[0]
    lo, hi = tr.window
    pieces = union_ns(_clip(tr.ops[dev], tr.window))
    edges = [lo] + [x for p in pieces for x in p] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [sp for sp in tr.host if sp[0] != WINDOW_SPAN]
    out = []
    for s, e in gaps[:n]:
        best, best_key = "host", (0.0, 0.0)
        for name, hs, he in spans:
            cover = min(e, he) - max(s, hs)
            # most cover first, then the shortest (innermost) span
            key = (cover, -(he - hs))
            if cover > 0 and key > best_key:
                best, best_key = name, key
        out.append([best, (e - s) * 1e-9])
    return out
