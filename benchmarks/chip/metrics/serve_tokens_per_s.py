"""Output tokens whose step returned inside the window, over the
window's length."""


def read(run):
    d = run.runner
    n = sum(1 for r in d.recs for t in r.token_times if d.t0 <= t < d.close)
    return n / (d.close - d.t0)
