"""The least time the chip could take to read and write the recurrent
state of the slots a pure-decode step updates, over the time the state
update took. Bytes bound it: the slots a such step updates (the mean of
the spans' `state_rows`, which only the engine knows) x the bytes of a
slot's state over the delta-rule layers
(solar2_events.state_bytes_a_slot: float32, heads x d x d a layer), read
once and written once, over the chip's HBM bandwidth. The one-token
operands and outputs are left out, and the kernel also streams the slots
that stand idle: the share is a floor."""

from benchmarks import solar2_events


def read(run):
    took = solar2_events.seconds_a_step(run, solar2_events.STATE)
    rows = solar2_events.span_mean(run, lambda args: args["state_rows"])
    if not took or rows is None:
        return None
    moved = 2 * rows * solar2_events.state_bytes_a_slot(run.config)
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / took
