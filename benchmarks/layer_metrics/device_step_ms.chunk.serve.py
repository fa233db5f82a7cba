"""Median device milliseconds of a step that carries a prefill chunk
beside the decoding slots: as `device_step_ms.decode.serve`, over the
traced window's steps of kind `chunk` (every bucket together; the table
that benchmarks/device_steps.py prints has them apart)."""

from benchmarks import device_steps


def read(run):
    return device_steps.step_ms(run, "chunk")
