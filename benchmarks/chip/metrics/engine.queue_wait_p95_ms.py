"""ServeEngine: 95th percentile of due time to admission (the engine's
``t_admit``, set when the request's prefill returned), over the requests
due in the traced part of the window."""

from benchmarks.chip.readings import in_trace, p95


def read(run):
    d = run.runner
    return p95((r.req.t_admit - r.due) * 1e3 for r in d.recs
               if r.req is not None and r.req.t_admit is not None
               and in_trace(d, r.due, r.due))
