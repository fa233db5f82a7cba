"""Model FLOP/s utilization of an LFM2-MoE training step on one chip's
part of the experts: the FLOPs a token needs on the experts it chose that
are HELD here (forward and backward, no recomputation;
lfm2_counts.held_flops_per_token, the held assignments a token from the
window's `assignments_held`) x tokens per second of this window, over
chips x the chip's bf16 peak. None on a run that is not this family's (no
`layer_types`, or no `assignments_held` among the counters)."""

from benchmarks import harness

counts = harness.load_module("lfm2_counts.py")


def read(run):
    tok_s = run.result["end_to_end"].get("train_tok_s")
    held = run.result["counters"].get("assignments_held")
    tokens = run.result["counters"].get("tokens")
    if (tok_s is None or not held or not tokens
            or "layer_types" not in run.config):
        return None
    flops = counts.held_flops_per_token(
        run.config, run.traffic["sequence_length"], sum(held) / tokens)
    return 100.0 * flops * tok_s / (run.chips
                                    * run.peaks["bf16_flops_per_s"])
