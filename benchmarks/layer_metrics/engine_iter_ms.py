"""Median harness-clock milliseconds of one engine.step() in the window."""

from benchmarks import harness


def read(run):
    step_s = run.result["counters"].get("step_s")
    return harness.median(step_s) * 1e3 if step_s else None
