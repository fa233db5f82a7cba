"""Device milliseconds a step in the rest of the state-space layers (chip
0; the input projection, the convolution, W_x with the three inner norms
and dt, the gate and the output projection, of the slots' rows and of a
chunk's; 26 layers; every step, with a chunk or without): jamba2_events.py
says how they are found."""

from benchmarks import jamba2_events


def read(run):
    return jamba2_events.per_step_ms(run, jamba2_events.MIX)
