"""The least time the chip could take for a step's grouped causal
attention calls, from the work (lfm2_counts.grouped_attention_least_seconds:
six matmuls of 2 s^2 x 64 a query head halved by the mask, q-sized arrays
at 32 heads and k-sized ones at 8; FLOP-bound), over the time of the
`flash_attention*` events. None on a run that is not this family's."""

from benchmarks import harness, lfm2_events

counts = harness.load_module("lfm2_counts.py")


def read(run):
    took_ms = lfm2_events.named_ms(
        run, lambda name: name.startswith("flash_attention"))
    if not took_ms:
        return None
    least, _bound = counts.grouped_attention_least_seconds(
        run.config, run.traffic["sequence_length"],
        run.traffic["global_batch"] // run.chips, run.peaks)
    return 100.0 * least * 1e3 / took_ms
