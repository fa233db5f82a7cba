"""Seconds of set-up under the program's `import` phase: what importing
`flexflow_tpu` cost, its own modules and whatever of JAX was not imported
yet (benchmarks/startup.py; nothing to read on a program that keeps no
start-up record)."""

from benchmarks import startup


def read(run):
    return startup.metric(run, startup.import_s)
