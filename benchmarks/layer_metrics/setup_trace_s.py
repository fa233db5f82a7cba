"""Seconds of set-up JAX spent tracing the program's functions to jaxprs
and lowering them to MLIR: the union of the `build.trace` and
`build.lower` events on each thread (they nest), summed over threads as
`xla_compile_s` sums the backend's. What an unrolled kernel body or an
unrolled trunk costs at every start, whatever the compile cache holds
(benchmarks/startup.py prints the two apart; nothing to read on a program
that keeps no start-up record)."""

from benchmarks import startup


def read(run):
    return startup.metric(run, startup.trace_s)
