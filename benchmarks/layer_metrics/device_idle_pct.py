"""Share of the traced window in which no operation ran on chip 0."""


def read(run):
    return run.trace.idle_pct(0)
