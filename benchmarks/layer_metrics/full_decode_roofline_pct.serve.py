"""The least time the chip could take to read the keys and values the
global layers' attention reads, over the time it took, in the steps that
only decode. Bytes bound it: a step's `kv_rows` (the context rows its slots
hold, which only the engine knows) x the bytes of a token's keys and values
over the global layers held (mimo2_events.bytes_a_row, in the step's
`kv_itemsize`), over the chip's HBM bandwidth. Queries, outputs and the
page tables are left out: the share is a floor."""

from benchmarks import mimo2_events


def read(run):
    return mimo2_events.roofline_pct(run, mimo2_events.FULL, "kv_rows",
                                     window=False)
