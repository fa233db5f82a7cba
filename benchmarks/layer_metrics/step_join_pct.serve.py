"""Share of the steps dispatched and fetched inside the traced window that
benchmarks/device_steps.py joined to exactly one execution on the device,
the pairing sound: 100, or the join is at fault and the readers of a
step's time return nothing. Nothing to read on a program whose spans
carry no `step`, or in a trace without the per-executable line."""

from benchmarks import device_steps


def read(run):
    found = device_steps.record(run)
    if not found or not found.dispatched:
        return None
    return 100.0 * len(found.steps) / found.dispatched
