"""Seconds of the phases `compile` and `serve.compile` that lie under no
phase inside them and no build of their thread: the hole in the program's
own record of its start, which says how far the other `setup_*` readers
can be believed (benchmarks/startup.py; nothing to read on a program that
keeps no start-up record)."""

from benchmarks import startup


def read(run):
    return startup.metric(run, startup.unnamed_s)
