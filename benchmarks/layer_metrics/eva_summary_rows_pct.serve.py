"""The share of the rows the window's decoding rows attended that are chunk
summaries and not exact rows: the engine's `eva_summary_rows` over
`eva_exact_rows` + `eva_summary_rows`, both counted a layer over the
window's steps (evabyte_events.summary_rows_pct)."""

from benchmarks import evabyte_events


def read(run):
    return evabyte_events.summary_rows_pct(run)
