"""Device milliseconds a step in multi-query paged decode (chip 0; the
Pallas kernel `flash_attention_paged_decode_grouped`, 20 query heads over
one KV head, the two softmax layers, the slots' rows; every step, with a
chunk or without): jamba2_events.py says how it is found.
(`gqa_decode_ms.serve`'s reader cuts the device's steps by Solar-Open2's
`gqa_layers` and cannot take this configuration.)"""

from benchmarks import jamba2_events


def read(run):
    return jamba2_events.per_step_ms(run, jamba2_events.ATTEND)
