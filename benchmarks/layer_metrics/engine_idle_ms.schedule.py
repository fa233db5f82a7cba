"""Milliseconds an engine iteration that chip 0 sits idle while the
program's innermost span is serve.schedule (admissions, page tables, the
chunk choice, building the step's arrays) or serve.bookkeep (tokens,
completions, after the fetch): Python between two device calls."""

from benchmarks import program_spans


def read(run):
    return program_spans.engine_idle_ms(
        run, ("ff/serve.schedule", "ff/serve.bookkeep"))
