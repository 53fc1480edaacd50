"""Tokens of every step that started in the window, over the time from
the window's opening to the end of the last of them (each step ends
when its loss has reached the host)."""


def read(run):
    d = run.runner
    if not d.steps:
        return None
    return len(d.steps) * d.tokens_per_step / (d.steps[-1][1] - d.t0)
