"""Device milliseconds a pure-decode step in the shared experts (chip 0;
the four averaged experts of each of the four layers, held as one gated
MLP 16,384 wide; scope `moe.shared`): cmdap_events.py says how they are
found."""

from benchmarks import cmdap_events


def read(run):
    return cmdap_events.per_step_ms(run, cmdap_events.SHARED)
