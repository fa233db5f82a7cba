"""Seconds of set-up spent making and placing the weights: the union, on
the job's thread, of the phases `compile.init` (the initializers, the
optimizer's slots, their placement) and `serve.adopt` (the decode graph's
casts and copies), the builds inside them included
(benchmarks/startup.py prints the two apart; nothing to read on a program
that keeps no start-up record)."""

from benchmarks import startup


def read(run):
    return startup.metric(run, startup.weights_s)
