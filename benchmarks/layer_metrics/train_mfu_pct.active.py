"""Model FLOP/s utilization of a routed-expert LM's training step: the
FLOPs a token needs on the experts it is routed to (forward and backward,
no recomputation; olmoe_counts.active_flops_per_token) x tokens per second
of this window, over chips x the chip's bf16 peak."""

from benchmarks import harness

counts = harness.load_module("olmoe_counts.py")


def read(run):
    tok_s = run.result["end_to_end"].get("train_tok_s")
    if tok_s is None or "num_experts_per_tok" not in run.config:
        return None
    flops = counts.active_flops_per_token(run.config,
                                          run.traffic["sequence_length"])
    return 100.0 * flops * tok_s / (run.chips
                                    * run.peaks["bf16_flops_per_s"])
