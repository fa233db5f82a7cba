"""Programs the backend built, or read from the compile cache, during
set-up: the `build.backend` events that ended in it
(benchmarks/startup.py; nothing to read on a program that keeps no
start-up record)."""

from benchmarks import startup


def read(run):
    return startup.metric(run, startup.programs)
