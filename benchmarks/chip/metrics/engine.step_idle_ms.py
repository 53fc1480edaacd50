"""ServeEngine: mean milliseconds in which the chip ran no operation
inside one ``engine.step`` span of the traced part (admissions, the
tick, its host syncs and the drain)."""

from benchmarks.chip.program_spans import mean_idle_ms


def read(run):
    return mean_idle_ms(run, "engine.step")
