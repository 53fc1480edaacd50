"""runtime/serve_step.py: device milliseconds of one engine-tick
program, from the trace."""

from benchmarks.chip.readings import tick_device_s


def read(run):
    t = tick_device_s(run)
    return None if t is None else t * 1e3
