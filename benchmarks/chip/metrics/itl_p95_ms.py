"""95th percentile of every gap between consecutive output tokens of a
request, the later token inside the window."""

from benchmarks.chip.readings import p95


def read(run):
    d = run.runner
    return p95((b - a) * 1e3 for r in d.recs
               for a, b in zip(r.token_times, r.token_times[1:])
               if d.t0 <= b < d.close)
