"""Device milliseconds a pure-decode step in the core of the layers that
attend their whole past (chip 0; the paged decode kernel over every slot's
context in the global layers; scope `gqa.attend`): mimo2_events.py says how
they are found."""

from benchmarks import mimo2_events


def read(run):
    return mimo2_events.per_step_ms(run, mimo2_events.FULL)
