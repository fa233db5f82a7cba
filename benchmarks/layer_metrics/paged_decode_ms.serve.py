"""Device milliseconds in the paged decode kernel (chip 0's events named
flash_attention_paged_decode*, all layers) per iteration that ran it
(program_spans.decode_kernel_steps: the pure-decode steps)."""

from benchmarks import program_spans


def read(run):
    steps = program_spans.decode_kernel_steps(run)
    seconds = program_spans.decode_kernel_seconds(run)
    if not steps or not seconds:
        return None
    return seconds / len(steps) * 1e3
