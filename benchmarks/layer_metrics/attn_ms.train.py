"""Device milliseconds a step spends in the flash attention kernels
(trace events named flash_attention*, chip 0, forward and backward)."""


def read(run):
    steps = run.result["counters"].get("steps")
    if not steps:
        return None
    seconds = run.trace.seconds_of(
        lambda name: name.startswith("flash_attention"))
    return seconds / steps * 1e3 if seconds else None
