"""Device milliseconds a step in the selective scan's state update of the
slots' rows (chip 0; the Pallas kernel `selective_scan_update`, 256 rows
of one token, and what feeds it under the scope `ssm.state`; the 26
state-space layers; every step, with a chunk or without: a chunk's own
calls of the kernel are `ssm_scan_ms.serve`'s): jamba2_events.py says how
they are found."""

from benchmarks import jamba2_events


def read(run):
    return jamba2_events.per_step_ms(run, jamba2_events.STATE)
