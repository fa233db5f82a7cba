"""Device milliseconds a pure-decode step inside `mla.attend` (chip 0; the
paged latent kernel that reads every slot's whole latent history, and W_uv
after it; all layers), from the step's own interval on the device:
ms4_events.py says how they are found."""

from benchmarks import ms4_events


def read(run):
    return ms4_events.per_step_ms(run, ms4_events.ATTEND)
