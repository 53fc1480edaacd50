"""ServeEngine: mean over the engine steps in the traced part of the
window of the tokens a step ticked over the engine's slots, in percent."""

from benchmarks.chip.readings import in_trace


def read(run):
    d = run.runner
    ticks = [t for t in d.ticks if in_trace(d, t[0], t[1])]
    if not ticks:
        return None
    return 100.0 * sum(t[2] for t in ticks) / (len(ticks) * d.slots)
