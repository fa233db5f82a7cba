"""Model FLOP/s utilization of the whole training step: the FLOPs a token
needs (forward and backward, no recomputation) x tokens per second of this
window, over chips x the chip's bf16 peak."""

from benchmarks import harness


def read(run):
    tok_s = run.result["end_to_end"].get("train_tok_s")
    if tok_s is None:
        return None
    flops = harness.flops_per_token(run.config,
                                    run.traffic["sequence_length"])
    return 100.0 * flops * tok_s / (run.chips
                                    * run.peaks["bf16_flops_per_s"])
