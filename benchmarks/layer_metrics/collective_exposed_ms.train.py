"""Milliseconds a step (an `ff/step` span of the fit loop) in which a
collective is open on chip 0 (collective_ms.train's events, ops or async
line) and no other operation runs: the part of collective_ms.train that
nothing hides."""

from benchmarks import program_spans, trace


def read(run):
    steps = program_spans.count(run, "ff/step")
    if not steps:
        return None
    return program_spans.device_seconds_while(
        run, lambda name: bool(trace.COLLECTIVE.match(name)),
        alone=True) / steps * 1e3
