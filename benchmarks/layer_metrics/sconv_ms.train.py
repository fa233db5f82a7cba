"""Device milliseconds a step in the gated short convolutions' events
(chip 0; the scopes `sconv.proj`, `sconv.conv`, `sconv.out`; forward and
backward; all `conv` layers): lfm2_events.py says how they are found.
None on a run without them."""

from benchmarks import lfm2_events


def read(run):
    return lfm2_events.scope_ms(run, "sconv.")
