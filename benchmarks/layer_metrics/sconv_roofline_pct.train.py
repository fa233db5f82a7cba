"""The least time the chip could take for a step's short-convolution
projections, from their shapes (lfm2_counts.short_conv_least_seconds: six
matmuls a `conv` layer, FLOP-bound at the cell's shapes), over the time of
everything under `sconv.*` (sconv_ms.train: the projections, the gates'
products and the taps). None on a run without those events."""

from benchmarks import harness, lfm2_events

counts = harness.load_module("lfm2_counts.py")


def read(run):
    took_ms = lfm2_events.scope_ms(run, "sconv.")
    if not took_ms:
        return None
    tokens = (run.traffic["global_batch"] // run.chips
              * run.traffic["sequence_length"])
    least, _bound = counts.short_conv_least_seconds(run.config, tokens,
                                                    run.peaks)
    return 100.0 * least * 1e3 / took_ms
