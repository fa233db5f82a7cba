"""Device milliseconds a chunk step in the multi-query paged attention
kernel of a prefill chunk (chip 0's events named
flash_attention_paged_chunk*, all layers), over the traced window's
`ff/serve.prefill` spans with more than one token: totals over a count,
no join of events to spans by time. A program in which no such event ran
(a parent commit, a chunk the kernel's gate refused) has nothing to
read."""

from benchmarks import program_spans


def read(run):
    steps = [s for s in program_spans.named(run, "ff/serve.prefill")
             if s[3].get("tokens", 0) > 1]
    seconds = run.trace.seconds_of(
        lambda name: name.startswith("flash_attention_paged_chunk"))
    if not steps or not seconds:
        return None
    return seconds / len(steps) * 1e3
