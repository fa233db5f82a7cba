"""The least time the chip could take to read the keys and values the
attention over the selected rows reads, over the time it took, in the
steps that only decode. Bytes bound it: a step's `sel_rows` (the sum over
its slots of min(context, topk), which only the engine knows) x the bytes
of a token's keys and values over the layers held
(keye2_events.attend_bytes_a_row, in the step's `kv_itemsize`), over the
chip's HBM bandwidth. A selected row is read at least once whatever
implements the read (XLA's gather today, a kernel later); queries,
indices and outputs are left out: the share is a floor."""

from benchmarks import keye2_events


def read(run):
    return keye2_events.roofline_pct(
        run, keye2_events.ATTEND,
        lambda args: args["sel_rows"] * keye2_events.attend_bytes_a_row(
            run.config, args["kv_itemsize"]))
