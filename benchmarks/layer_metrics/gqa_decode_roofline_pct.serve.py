"""The least time the chip could take to read the keys and values the
paged decode kernel ran over, over the time the kernel took, in a step
that only decodes. Bytes bound it: the context rows of a such step's
slots (the mean of the spans' `kv_rows`) x the bytes of a row's keys and
values over the softmax layers held (solar2_events.kv_bytes_a_row:
num_key_value_heads x head_dim wide, in the span's `kv_itemsize`), over
the chip's HBM bandwidth. Queries, outputs and page tables are left out,
and a row counts once although the kernel fetches whole blocks: the share
is a floor."""

from benchmarks import solar2_events


def read(run):
    took = solar2_events.seconds_a_step(run, ("gqa.attend",))
    moved = solar2_events.span_mean(
        run, lambda args: args["kv_rows"] * solar2_events.kv_bytes_a_row(
            run.config, args["kv_itemsize"]))
    if not took or moved is None:
        return None
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / took
