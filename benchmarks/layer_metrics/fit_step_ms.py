"""Median over the window's fit calls of seconds / steps, host clock ending
in block_until_ready."""

from benchmarks import harness


def read(run):
    per_step = run.result["counters"].get("call_step_s")
    return harness.median(per_step) * 1e3 if per_step else None
