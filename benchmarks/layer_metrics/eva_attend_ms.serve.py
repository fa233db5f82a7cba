"""Device milliseconds a pure-decode step in the core of the EVA layers
(chip 0; every slot's row over the exact rows of its aligned window and the
summaries of the windows closed before it, 8 layers; scope `eva.attend`,
however many kernel calls implement it): evabyte_events.py says how they
are found."""

from benchmarks import evabyte_events


def read(run):
    return evabyte_events.per_step_ms(run, evabyte_events.ATTEND)
