"""Whole training step: model FLOPs per token (forward and backward,
causal attention, no recomputation) times the window's tokens per
second, over the bf16 peak, in percent."""


def read(run):
    d = run.runner
    if run.trace is None or run.peaks is None or not d.steps:
        return None
    step = run.costs.train_step(run.cell.config, d.batch, d.seq)["flops"]
    rate = len(d.steps) / (d.steps[-1][1] - d.t0)
    return 100.0 * step * rate / run.peaks.bf16_flops
