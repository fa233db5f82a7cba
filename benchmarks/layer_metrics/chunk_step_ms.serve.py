"""Median harness-clock milliseconds of an engine.step() that carried a
turn's prefill chunk beside the decoding slots (the engine's prefill_calls
count rose during the call). The device readers of `dsv32-serve-sessions`
see the iterations that only decode; this is the other kind of step."""

from benchmarks import harness


def read(run):
    step_s = run.result["counters"].get("prefill_step_s")
    return harness.median(step_s) * 1e3 if step_s else None
