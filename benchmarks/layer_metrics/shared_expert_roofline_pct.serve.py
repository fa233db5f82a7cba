"""The least time the chip could take to read the shared experts' weights,
over the time their events took, in the steps that only decode. Bytes
bound it: 32 rows against 4 layers x 4 experts x 3 matrices of 4,096 x
4,096 (cmdap_events.shared_expert_bytes, at the item size the decode model
holds its weights in), read once a step, over the chip's HBM bandwidth.
The rows and the outputs are left out: the share is a floor."""

from benchmarks import cmdap_events


def read(run):
    return cmdap_events.shared_roofline_pct(run)
