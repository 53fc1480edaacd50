"""Kernels: the engine tick's share of its roofline.  For each tick in
the traced part, the least time the chip needs is the larger of its
FLOPs over the bf16 peak and its bytes (weights once, the KV rows its
slots attend to) over HBM bandwidth, from ``costs``; the share is their
sum over the ticks' device time, in percent."""

from benchmarks.chip.readings import serve_ticks, tick_device_s


def read(run):
    per_tick = tick_device_s(run)
    ticks = serve_ticks(run)
    if per_tick is None or not ticks or run.peaks is None:
        return None
    least = 0.0
    for _, _, _, ctx in ticks:
        if ctx:
            w = run.costs.decode_tick(run.cell.config, ctx)
            least += max(w["flops"] / run.peaks.bf16_flops,
                         w["bytes"] / run.peaks.hbm_bytes_per_s)
    return 100.0 * least / (per_tick * len(ticks))
