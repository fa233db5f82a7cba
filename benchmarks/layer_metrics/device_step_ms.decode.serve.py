"""Median device milliseconds of a step that only decodes: the interval of
the step's own execution on chip 0 (first to last operation, the
`XLA Modules` line), over the traced window's steps of kind `decode`
joined to their `ff/serve.dispatch` span by the step's id
(benchmarks/device_steps.py). Nothing to read on a program whose spans
carry no `step`, or where the join left a step out."""

from benchmarks import device_steps


def read(run):
    return device_steps.step_ms(run, "decode")
