"""TrainLoop: mean milliseconds in which the chip ran no operation
inside one ``train.step`` span of the traced part (the dispatch of the
jitted step)."""

from benchmarks.chip.program_spans import mean_idle_ms


def read(run):
    return mean_idle_ms(run, "train.step")
