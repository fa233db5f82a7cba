"""Milliseconds an engine iteration that chip 0 sits idle while the
program's innermost span is serve.dispatch or serve.fetch: the launch
before the step's first operation, the copy-out and the host's wake-up
after its last."""

from benchmarks import program_spans


def read(run):
    return program_spans.engine_idle_ms(
        run, ("ff/serve.dispatch", "ff/serve.fetch"))
