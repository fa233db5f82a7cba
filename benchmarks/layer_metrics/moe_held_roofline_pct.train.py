"""The least time the chip could take for a step's grouped matmuls over
the rows that went to HELD experts (lfm2_counts.held_matmul_least_seconds:
nine matmuls an expert layer over the window's `assignments_held` a step,
the held experts' matrices read once each), over the time of the `gmm*` /
`tgmm*` / `ragged-dot*` events. The accepted `moe_roofline_pct.train`
counts every expert's rows from OLMoE's keys and cannot take this cell.
None on a run that is not this family's."""

from benchmarks import harness, lfm2_events, moe_events

counts = harness.load_module("lfm2_counts.py")


def read(run):
    held = run.result["counters"].get("assignments_held")
    steps = run.result["counters"].get("steps")
    took_ms = lfm2_events.named_ms(run, moe_events.is_grouped_matmul)
    if not took_ms or not held or not steps:
        return None
    least, _bound = counts.held_matmul_least_seconds(
        run.config, sum(held) / steps / run.chips, run.peaks)
    return 100.0 * least * 1e3 / took_ms
