"""Whole serving step: model FLOPs of every prefill and decode token in
the traced part over its length times the bf16 peak, in percent."""

from benchmarks.chip.readings import serve_prefills, serve_ticks


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    c = run.cell.config
    flops = sum(run.costs.prefill(c, n)["flops"] for n in serve_prefills(run))
    flops += sum(run.costs.decode_tick(c, ctx)["flops"]
                 for *_, ctx in serve_ticks(run) if ctx)
    if not flops:
        return None
    return 100.0 * flops / (run.trace.window_s * run.peaks.bf16_flops)
