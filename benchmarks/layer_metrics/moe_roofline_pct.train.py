"""The least time the chip could take for a step's grouped matmuls, from
their shapes (olmoe_counts.grouped_matmul_least_seconds: FLOP-bound at the
cell's shapes), over the time their events took."""

from benchmarks import harness, moe_events

counts = harness.load_module("olmoe_counts.py")


def read(run):
    took_ms = moe_events.per_step_ms(
        run, lambda name, scope: moe_events.is_grouped_matmul(name))
    if not took_ms:
        return None
    tokens = (run.traffic["global_batch"] // run.chips
              * run.traffic["sequence_length"])
    least, _bound = counts.grouped_matmul_least_seconds(
        run.config, tokens, run.peaks)
    return 100.0 * least * 1e3 / took_ms
