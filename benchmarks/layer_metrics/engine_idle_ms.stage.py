"""Milliseconds an engine iteration that chip 0 sits idle while the
program's innermost span is serve.prepare_writes, serve.cow_copy or
serve.stage: block allocation, and the host-to-device puts of the step's
inputs, before the step is dispatched."""

from benchmarks import program_spans


def read(run):
    return program_spans.engine_idle_ms(
        run, ("ff/serve.prepare_writes", "ff/serve.cow_copy",
              "ff/serve.stage"))
