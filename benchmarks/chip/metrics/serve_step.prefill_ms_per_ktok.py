"""runtime/serve_step.py: device milliseconds of the prefill programs
in the trace per 1,000 prompt tokens admitted while it was taken."""

from benchmarks.chip.readings import PREFILL, serve_prefills


def read(run):
    if run.trace is None:
        return None
    from benchmarks.chip.trace import program_times
    _, total = program_times(run.trace).get(PREFILL, (0, 0.0))
    tokens = sum(serve_prefills(run))
    if not tokens or not total:
        return None
    return total * 1e3 / (tokens / 1e3)
