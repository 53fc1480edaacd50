"""Kernels: the prefill programs' share of their roofline.  For each
prompt admitted in the traced part, the least time the chip needs is
the larger of its FLOPs over the bf16 peak and its bytes (weights once,
the cache rows it writes) over HBM bandwidth, from ``costs.prefill``;
the share is their sum over the device time of the ``jit_prefill``
programs in the trace, in percent."""

from benchmarks.chip.readings import PREFILL, serve_prefills


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    from benchmarks.chip.trace import program_times
    _, total = program_times(run.trace).get(PREFILL, (0, 0.0))
    lengths = serve_prefills(run)
    if not lengths or not total:
        return None
    least = 0.0
    for n in lengths:
        w = run.costs.prefill(run.cell.config, n)
        least += max(w["flops"] / run.peaks.bf16_flops,
                     w["bytes"] / run.peaks.hbm_bytes_per_s)
    return 100.0 * least / total
