"""Device milliseconds a pure-decode iteration in latent attention over
the selected rows (chip 0; the gather from the pool, scores, softmax, the
weighted sum and W_uv; all layers): dsv32_events.py says how they are
found."""

from benchmarks import dsv32_events


def read(run):
    return dsv32_events.per_step_ms(run, dsv32_events.ATTEND)
