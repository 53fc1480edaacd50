"""ServeEngine: blocking device-to-host reads (``engine.sync`` spans)
per ``engine.step`` span, both in the traced part."""

from benchmarks.chip.program_spans import traced


def read(run):
    steps = traced(run, "engine.step")
    return len(traced(run, "engine.sync")) / len(steps) if steps else None
