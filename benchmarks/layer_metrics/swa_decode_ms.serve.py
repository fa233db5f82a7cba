"""Device milliseconds a pure-decode step in the core of the layers that
attend a window (chip 0; the paged decode kernel over the blocks that hold
every slot's last 128 rows in the window layers; scope `swa.attend`):
mimo2_events.py says how they are found."""

from benchmarks import mimo2_events


def read(run):
    return mimo2_events.per_step_ms(run, mimo2_events.WINDOW)
