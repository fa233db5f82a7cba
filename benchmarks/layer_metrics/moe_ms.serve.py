"""Device milliseconds a pure-decode iteration in the expert layers (chip
0; route, dispatch, grouped matmuls, combine, the shared expert; all
layers): dsv32_events.py says how they are found."""

from benchmarks import dsv32_events


def read(run):
    return dsv32_events.per_step_ms(run, dsv32_events.MOE)
