"""Device milliseconds a pure-decode iteration in grouped-KV paged decode
(chip 0; the Pallas kernel `flash_attention_paged_decode_grouped`, the one
softmax layer held): solar2_events.py says how it is found."""

from benchmarks import solar2_events


def read(run):
    return solar2_events.per_step_ms(run, ("gqa.attend",))
