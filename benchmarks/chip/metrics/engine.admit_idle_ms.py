"""ServeEngine: mean milliseconds in which the chip ran no operation
inside one ``engine.admit`` span of the traced part (the prefill, the
read of its first token and the splice into the slot)."""

from benchmarks.chip.program_spans import mean_idle_ms


def read(run):
    return mean_idle_ms(run, "engine.admit")
