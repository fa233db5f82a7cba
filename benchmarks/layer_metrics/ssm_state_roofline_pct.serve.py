"""The least time the chip could take to read and write the recurrent
state of the rows a step decodes, over the time their state update took.
Bytes bound it: the bytes of h a step's decoding rows read and write in a
layer (the spans' `ssm_state_bytes`, which only the engine can count) x
the 26 state-space layers, over the chip's HBM bandwidth, over the time
under `ssm.state` (`ssm_state_ms.serve`'s). The one-token operands and
outputs are left out, and the kernel also streams the slots that stand
idle: the share is a floor."""

from benchmarks import jamba2_events


def read(run):
    return jamba2_events.state_roofline_pct(run)
