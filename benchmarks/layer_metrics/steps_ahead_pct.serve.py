"""Share of the traced window's `ff/serve.fetch` spans whose step was
fetched with the next one already dispatched (the span's `ahead` is 1):
how often the engine kept a step in flight, so that the host's work of an
iteration ran beside the device's step and not between two of them. A
program whose fetch spans carry no `ahead` (a parent commit) has nothing
to read."""

from benchmarks import program_spans


def read(run):
    ahead = [stats["ahead"] for _, _, _, stats
             in program_spans.named(run, "ff/serve.fetch")
             if "ahead" in stats]
    if not ahead:
        return None
    return 100.0 * sum(1 for a in ahead if a) / len(ahead)
