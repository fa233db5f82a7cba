"""Seconds of set-up FlexFlow's own planning took: the union, on the job's
thread, of the phases `warmstart.plan_lookup`, `warmstart.calibration_load`,
`warmstart.store`, `compile.calibrate`, `compile.search`,
`compile.update_sharding` and `compile.verify` of every compile of the
process, the training graph's and the decode graph's
(benchmarks/startup.py; nothing to read on a program that keeps no
start-up record)."""

from benchmarks import startup


def read(run):
    return startup.metric(run, startup.search_s)
