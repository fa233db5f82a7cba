"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference tests multi-GPU only on real hardware (SURVEY §4); we do better
by unit-testing all SPMD logic on XLA's host platform with
--xla_force_host_platform_device_count=8, so sharding/search/collective code
is exercised in CI without TPUs.

`JAX_PLATFORMS=cpu` in the environment is what holds JAX to the CPU (the
tier-1 command sets it; it is defaulted here so a bare `pytest` does the
same). Pallas kernels run in interpret mode on this backend; what only the
TPU's compiler can refuse is covered by tests/test_chip_compile.py, which
compiles the main path's kernels ahead of time for a described v5e. Nothing
here runs on a chip — that is benchmarks/run.py's job.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax
import numpy as np
import pytest

assert jax.devices()[0].platform == "cpu", jax.devices()
assert jax.device_count() == 8, jax.devices()


@pytest.fixture
def rng():
    return np.random.RandomState(0)
