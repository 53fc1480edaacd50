"""ServeEngine: mean milliseconds in which the chip ran no operation
inside one ``engine.tick`` span of the traced part (the launch of the
tick program, its host syncs and the drain)."""

from benchmarks.chip.program_spans import mean_idle_ms


def read(run):
    return mean_idle_ms(run, "engine.tick")
