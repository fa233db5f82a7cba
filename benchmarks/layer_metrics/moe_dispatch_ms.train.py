"""Device milliseconds a step in the expert layer's events that are not
its grouped matmuls: the router, the sort, the gathers into and out of
sorted order, the gate-weighted sum, and their backward."""

from benchmarks import moe_events


def read(run):
    return moe_events.per_step_ms(
        run, lambda name, scope: not moe_events.is_grouped_matmul(name))
