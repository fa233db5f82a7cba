"""Device milliseconds a pure-decode iteration in the lightning indexer
(chip 0; the indexer's projections, its scores over the cached keys and
the top-k; all layers): dsv32_events.py says how they are found."""

from benchmarks import dsv32_events


def read(run):
    return dsv32_events.per_step_ms(run, dsv32_events.INDEX)
