"""Operator definition framework.

The reference implements each operator as {FFModel builder, Op subclass with
Legion task launchers, Params struct, OpMeta, CUDA kernels}
(pattern documented at src/ops/linear.cc). On TPU the per-device kernel is XLA
HLO traced from a pure function, and the Legion launcher disappears: an
operator here is

  - a frozen Params dataclass (the analog of `*_params.h`, used for op dedup
    and as the simulator cache key — reference include/flexflow/operator_params.h)
  - shape/weight inference (the analog of the builder's output-shape logic)
  - a pure `forward` (params, inputs, weights, state) → (outputs, state)
    traced under jit; autodiff replaces hand-written backward tasks
  - an analytic flop/byte count used by the Unity cost model in place of
    on-device `measure_operator_cost` when microbenchmarks are disabled.

State is threaded functionally for the few stateful ops (BatchNorm running
stats, Cache) — the TPU equivalent of OpMeta mutable fields.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

from ..fftype import DataType, OperatorType


@dataclass(frozen=True)
class WeightSpec:
    """Declares one trainable (or stateful) tensor of an operator."""

    name: str
    shape: tuple[int, ...]
    dtype: DataType
    initializer: str = "glorot_uniform"  # glorot_uniform|zeros|ones|normal|uniform
    trainable: bool = True


@dataclass
class OpContext:
    """Per-call execution context (the slim analog of OpMeta)."""

    training: bool = True
    rng: Any = None  # jax PRNG key folded per-op by the executor
    seq_length: int = -1
    profiling: bool = False
    mesh: Any = None  # global jax Mesh (for ops lowering to shard_map)
    # the plan's placement of this node — PartitionSpec of output 0 and
    # {weight name: PartitionSpec} — for ops that run a Pallas kernel per
    # shard (kernels/dispatch.per_shard) instead of leaving it to GSPMD
    out_spec: Any = None
    weight_axes: Any = None
    # MXU input dtype for matmul/conv when activations are fp32 — the TPU
    # analog of the reference's cublas tensor-op math mode
    # (allow_tensor_op_math_conversion, include/flexflow/config.h): inputs
    # are cast to this dtype, accumulation stays fp32.
    matmul_dtype: Any = None
    # overlap-capable collectives (ring attention's double-buffered hop
    # pipeline): False compiles the serial compute-then-hop schedule —
    # the ablation baseline matching the cost model's serial pricing
    # (FFConfig.overlap_collectives)
    overlap_collectives: bool = True


def matmul_cast(ctx: OpContext, *arrays):
    """Cast fp32 matmul operands to the MXU input dtype (no-op when the
    policy is off or activations are already low-precision)."""
    md = getattr(ctx, "matmul_dtype", None)
    if md is None:
        return arrays if len(arrays) > 1 else arrays[0]
    import jax.numpy as jnp

    out = tuple(a.astype(md) if a.dtype == jnp.float32 else a for a in arrays)
    return out if len(out) > 1 else out[0]


class OpDef:
    """Registry entry for one OperatorType."""

    def __init__(
        self,
        op_type: OperatorType,
        infer_shapes: Callable,  # (params, in_shapes) -> list[tuple]
        forward: Callable,  # (params, inputs, weights, state, ctx) -> (outputs, state)
        weights: Optional[Callable] = None,  # (params, in_shapes) -> list[WeightSpec]
        flops: Optional[Callable] = None,  # (params, in_shapes, out_shapes) -> float
        num_outputs: int = 1,
    ):
        self.op_type = op_type
        self.infer_shapes = infer_shapes
        self.forward = forward
        self.weights = weights or (lambda params, in_shapes: [])
        self.flops = flops or _default_flops
        self.num_outputs = num_outputs


def _default_flops(params, in_shapes, out_shapes) -> float:
    # elementwise-ish default: one flop per output element
    total = 0
    for s in out_shapes:
        total += math.prod(s) if s else 1
    return float(total)


_REGISTRY: dict[OperatorType, OpDef] = {}


def register_op(op_def: OpDef):
    _REGISTRY[op_def.op_type] = op_def
    return op_def


def get_op_def(op_type: OperatorType) -> OpDef:
    if op_type not in _REGISTRY:
        raise KeyError(f"no OpDef registered for {op_type!r}")
    return _REGISTRY[op_type]


def registered_ops() -> dict[OperatorType, OpDef]:
    return dict(_REGISTRY)
