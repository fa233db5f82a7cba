"""Weight initializers.

Reference: include/flexflow/initializer.h + initializer_kernel.cu — each a
Legion task over the weight's index space using curand. Here each initializer
is a pure function of a PRNG key; the executor gives every weight a distinct
key folded from the op/weight name, so results are reproducible regardless of
mesh shape or evaluation order (stronger determinism than the reference's
per-device curand streams).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp


class Initializer:
    def __call__(self, key, shape, dtype):
        raise NotImplementedError


@dataclass
class GlorotUniformInitializer(Initializer):
    seed: int = 0

    def __call__(self, key, shape, dtype):
        if len(shape) >= 2:
            fan_in, fan_out = shape[-2], shape[-1]
            receptive = math.prod(shape[:-2]) if len(shape) > 2 else 1
            fan_in *= receptive
            fan_out *= receptive
        else:
            fan_in = fan_out = shape[0] if shape else 1
        scale = math.sqrt(6.0 / (fan_in + fan_out))
        return jax.random.uniform(key, shape, dtype, -scale, scale)


@dataclass
class ZeroInitializer(Initializer):
    def __call__(self, key, shape, dtype):
        return jnp.zeros(shape, dtype)


@dataclass
class ConstantInitializer(Initializer):
    value: float = 0.0

    def __call__(self, key, shape, dtype):
        return jnp.full(shape, self.value, dtype)


@dataclass
class UniformInitializer(Initializer):
    seed: int = 0
    min_val: float = 0.0
    max_val: float = 1.0

    def __call__(self, key, shape, dtype):
        return jax.random.uniform(key, shape, dtype, self.min_val, self.max_val)


@dataclass
class NormInitializer(Initializer):
    seed: int = 0
    mean: float = 0.0
    stddev: float = 1.0

    def __call__(self, key, shape, dtype):
        return self.mean + self.stddev * jax.random.normal(key, shape, dtype)


@dataclass
class LogRangeInitializer(Initializer):
    """log(1), log(2), .. log(n) along the first axis of (n, channels),
    the same a channel: the start of a state-space layer's A_log (A =
    -exp(A_log) is then -1 .. -n, a state's n decay rates a channel)."""

    def __call__(self, key, shape, dtype):
        steps = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
        return jnp.broadcast_to(steps[:, None], shape).astype(dtype)


_BY_NAME = {
    "glorot_uniform": GlorotUniformInitializer(),
    "zeros": ZeroInitializer(),
    "ones": ConstantInitializer(1.0),
    "normal": NormInitializer(stddev=0.02),
    "uniform": UniformInitializer(),
}


def initializer_by_name(name: str) -> Initializer:
    return _BY_NAME[name]
