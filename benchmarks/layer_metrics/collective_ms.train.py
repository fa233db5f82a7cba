"""Device milliseconds a step spends in collectives on chip 0: all-reduce,
all-gather, reduce-scatter, collective-permute and all-to-all, from start
to done, hidden behind compute or not."""

from benchmarks import trace


def read(run):
    steps = run.result["counters"].get("steps")
    if not steps:
        return None
    seconds = run.trace.seconds_of(
        lambda name: bool(trace.COLLECTIVE.match(name)),
        lines=("ops", "async"))
    return seconds / steps * 1e3
