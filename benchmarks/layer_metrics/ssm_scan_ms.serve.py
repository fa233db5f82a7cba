"""Device milliseconds a step that carries a prefill chunk spends in the
chunk's scans (chip 0; the second call of `selective_scan_update` in each
of the 26 state-space layers: the chunk's tokens in order from its slot's
state, h in VMEM over them). The vector unit bounds a token of it, not
HBM, and `peaks.json` holds no peak for that: no roofline share is given.
jamba2_events.chunk_scan_ms says how the calls are told apart."""

from benchmarks import jamba2_events


def read(run):
    return jamba2_events.chunk_scan_ms(run)
