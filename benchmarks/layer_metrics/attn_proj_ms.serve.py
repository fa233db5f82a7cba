"""Device milliseconds a pure-decode step in the attention layers'
projections (chip 0; W_q and W_o of 4,096 x 16,384, W_k and W_v, the
rotation of the window layers' q and k; scopes `gqa.qkv`, `gqa.out`,
`swa.qkv`, `swa.out`; all four layers): cmdap_events.py says how they are
found."""

from benchmarks import cmdap_events


def read(run):
    return cmdap_events.per_step_ms(run, cmdap_events.PROJECTIONS)
