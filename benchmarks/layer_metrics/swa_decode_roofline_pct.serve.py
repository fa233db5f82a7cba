"""The least time the chip could take to read the keys and values the
window layers' attention attends, over the time it took, in the steps that
only decode. Bytes bound it: a step's `window_rows` (a slot's last 128
rows, or its context where that is shorter, summed over its slots) x the
bytes of a token's keys and values over the window layers held
(mimo2_events.bytes_a_row), over the chip's HBM bandwidth. A kernel that
reads whole blocks reads more than the window: that shows here as a lower
share. Queries, sinks and outputs are left out: the share is a floor."""

from benchmarks import mimo2_events


def read(run):
    return mimo2_events.roofline_pct(run, mimo2_events.WINDOW,
                                     "window_rows", window=True)
