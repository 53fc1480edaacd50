"""Device: the share of the traced part in which no operation ran on
the chip, in percent."""


def read(run):
    from benchmarks.chip.trace import busy_s
    if run.trace is None or not run.trace.window_s:
        return None
    busy = busy_s(run.trace)
    return None if busy is None else 100.0 * (1 - busy / run.trace.window_s)
