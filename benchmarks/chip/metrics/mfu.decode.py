"""Whole engine tick: model FLOPs of the ticks in the traced part over
the host time of those ticks (the ``bench.tick`` spans: launch, device
and the read-back of the slots' tokens) times the bf16 peak, in
percent."""

from benchmarks.chip.readings import serve_ticks


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    lo, hi = run.trace.window
    host = sum(e - s for n, s, e in run.trace.host
               if n == "bench.tick" and lo <= s and e <= hi) * 1e-9
    flops = sum(run.costs.decode_tick(run.cell.config, ctx)["flops"]
                for *_, ctx in serve_ticks(run) if ctx)
    if not host or not flops:
        return None
    return 100.0 * flops / (host * run.peaks.bf16_flops)
