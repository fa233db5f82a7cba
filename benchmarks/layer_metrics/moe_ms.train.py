"""Device milliseconds a step in the expert layer's events (chip 0;
route, dispatch, grouped matmuls, combine; forward and backward; all
layers): moe_events.py says how they are found."""

from benchmarks import moe_events


def read(run):
    return moe_events.per_step_ms(run, lambda name, scope: True)
