"""The least time the chip could take for a step's attention calls, from
their shapes, over the time they took (attn_ms.train). At these shapes
the FLOPs bound it, not the bytes (harness.attention_least_seconds)."""

from benchmarks import harness


def read(run):
    took_ms = harness.load_module(
        "layer_metrics", "attn_ms.train.py").read(run)
    if not took_ms:
        return None
    least, _bound = harness.attention_least_seconds(
        run.config, run.traffic["sequence_length"],
        run.traffic["global_batch"] // run.chips, run.peaks)
    return 100.0 * least * 1e3 / took_ms
