"""The least time the chip could take to read the rows the EVA layers'
core attends, over the time it took, in the steps that only decode. Bytes
bound it: a step's `eva_exact_rows` + `eva_summary_rows` (rows a layer: a
slot's (n mod 2,048) + 1 exact rows and 128 floor(n / 2,048) summaries at
context n) x 16,384 B x 8 layers (evabyte_events.row_bytes), over the
chip's HBM bandwidth. Counted from the span's rows whatever implements the
core: a kernel that reads whole pages reads more than the rows, which shows
here as a lower share. Queries, outputs and the merge are left out: the
share is a floor."""

from benchmarks import evabyte_events


def read(run):
    return evabyte_events.attend_roofline_pct(run)
