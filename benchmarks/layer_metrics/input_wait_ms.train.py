"""Host milliseconds a step inside the fit loop's `ff/data_wait` spans:
the batch slice and its device_put on the stepping thread. The eager loop
runs ahead of the device, so this is not device idle; it is what the
input costs the host of each step's time (fit_step_ms) before it would
set the pace."""

from benchmarks import program_spans


def read(run):
    steps = program_spans.count(run, "ff/step")
    if not steps:
        return None
    return program_spans.seconds_in(run, ("ff/data_wait",)) / steps * 1e3
