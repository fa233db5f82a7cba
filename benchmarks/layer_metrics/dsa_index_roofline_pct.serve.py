"""The least time the chip could take to read the indexer keys the
indexer scores, over the time the events under `dsa.index` took (the
indexer's projections and its scores; the top-k is `dsa.topk`'s), in the
steps that only decode. Bytes bound it: a step's `index_rows` (the sum of
its slots' contexts, which only the engine knows) x the bytes of a token's
indexer key over the layers held (keye2_events.index_bytes_a_row, in the
step's `kv_itemsize`), over the chip's HBM bandwidth. The indexer's
weights and the scores it writes are left out: the share is a floor."""

from benchmarks import keye2_events


def read(run):
    return keye2_events.roofline_pct(
        run, keye2_events.INDEX,
        lambda args: args["index_rows"] * keye2_events.index_bytes_a_row(
            run.config, args["kv_itemsize"]))
