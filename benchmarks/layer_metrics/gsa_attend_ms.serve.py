"""Device milliseconds a pure-decode step in the attention over the
selected rows (chip 0; the gather of each slot's selected rows of keys and
values, the scores, the softmax, the weighted sum; all layers):
keye2_events.py says how they are found."""

from benchmarks import keye2_events


def read(run):
    return keye2_events.per_step_ms(run, keye2_events.ATTEND)
