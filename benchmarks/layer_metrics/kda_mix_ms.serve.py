"""Device milliseconds a pure-decode iteration in the rest of the
delta-rule layers (chip 0; projections, convolution, decay and beta, the
gated norm and the output projection; three layers): solar2_events.py
says how they are found."""

from benchmarks import solar2_events


def read(run):
    return solar2_events.per_step_ms(run, solar2_events.MIX)
