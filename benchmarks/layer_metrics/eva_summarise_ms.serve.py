"""Device milliseconds a pure-decode step in the chunk summaries of the
EVA layers (chip 0; the 16 rows of the chunk a row completes read back,
summarised and written, 8 layers; scope `eva.summarise`): evabyte_events.py
says how they are found."""

from benchmarks import evabyte_events


def read(run):
    return evabyte_events.per_step_ms(run, evabyte_events.SUMMARISE)
