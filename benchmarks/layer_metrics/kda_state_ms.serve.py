"""Device milliseconds a pure-decode iteration in the delta rule's state
update (chip 0; the Pallas kernel `delta_rule_update` and what feeds it
under the scope `kda.state`; the three delta-rule layers):
solar2_events.py says how they are found."""

from benchmarks import solar2_events


def read(run):
    return solar2_events.per_step_ms(run, solar2_events.STATE)
