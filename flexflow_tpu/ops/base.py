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

from ..fftype import DataType, OperatorType, size_of_datatype


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


# how a leaf of a decode op's state is indexed: by block under a page table
# (blocks, block_size, ..), by slot and then position in the contiguous
# layout (slots, max_seq_len + 1, ..), by slot (slots, ..); LAST_CALL is a
# record of the slots' last call (what a row selected) no token carries on
BY_BLOCK, BY_POSITION, BY_SLOT, LAST_CALL = "block", "position", "slot", "call"
# what the state can follow: a block-by-block handoff to another engine, a
# cursor rewound behind written rows, a call of several query tokens a
# slot, a prefix matched in the pool
HANDOFF, REWIND, QUERIES, PREFIX = "handoff", "rewind", "queries", "prefix"


@dataclass(frozen=True)
class StateLeaf:
    name: str
    index: str    # BY_BLOCK | BY_POSITION | BY_SLOT | LAST_CALL
    width: tuple  # numbers a token (by block) or a slot (the others) holds
    dtype: DataType
    # a BY_BLOCK leaf's cache group (serving/paged.py): 0 the global one,
    # 1 the window group, whose pool, table and block ids are its own
    group: int = 0
    # positions a row of a BY_BLOCK leaf stands for: logical block j still
    # covers positions [j * block_size, (j + 1) * block_size), in
    # block_size // every rows
    every: int = 1


@dataclass(frozen=True)
class DecodeState:
    """What a decode op keeps from token to token, as its `OpDef.state`
    declares it: the op's weights function allocates from it, and serving/
    and executor.py size, price, copy, hand off and refuse by it."""

    leaves: tuple
    slots: int = 0       # slots the by-slot leaves hold; 0: a call's rows
    # blocks of the global group's pool and of the window group's, the
    # scratch block too: what the leaves of each group are allocated over
    blocks: int = 0
    window_blocks: int = 0
    block_size: int = 0  # positions a block covers, whatever the group
    window: int = 0      # > 0: the window group's leaves are read this far back
    # the window is aligned: a row at t reads from window * (t // window)
    # on, where the default (sliding) reads its nearest `window` rows
    window_aligned: bool = False
    selected: int = 0    # positions a row attends at the most; 0: all
    # {what the state cannot follow: what is said of the first such layer,
    # which completes "cannot serve a graph with", {layer} its name}
    cannot: dict = field(default_factory=dict)
    # whether a prefill chunk rides as single-query rows past the slots',
    # (mesh, itemsize) -> bool, and the query rows a tile of the chunk
    # kernel then takes, (mesh, itemsize, rows) -> int or None; None: never
    chunk_as_rows: Optional[Callable] = None
    chunk_query_tile: Optional[Callable] = None
    # whether a row of a (rows, 1) call reads its whole context by the paged
    # decode kernel's walk of its table row, (mesh, itemsize) -> bool; None:
    # never (the engine counts what the walk copies by this)
    rows_walk: Optional[Callable] = None
    # what one such layer reads and writes in a step, for the step's span
    # and the engine's totals: (positions of the step's decoding rows) ->
    # {counter: number}; None: nothing beyond what the engine counts
    step_counts: Optional[Callable] = None

    def names(self, *indexes, group: Optional[int] = None) -> tuple:
        """The leaves of those indexes, of one cache group where given."""
        return tuple(l.name for l in self.leaves if l.index in indexes
                     and group in (None, l.group))

    def weight_specs(self, rows: int) -> list:
        """The leaves as allocated, for a call of `rows` rows."""
        return [WeightSpec(
            l.name, ((self.window_blocks if l.group else self.blocks,
                      self.block_size // l.every) if l.index == BY_BLOCK
                     else (self.slots or rows,)) + l.width,
            l.dtype, "zeros", trainable=False) for l in self.leaves]

    def bytes_of(self, index: str, group: Optional[int] = None) -> int:
        """Bytes one block (BY_BLOCK; of one cache group where given) or
        one slot (BY_SLOT) holds."""
        return sum((self.block_size // l.every if index == BY_BLOCK else 1)
                   * math.prod(l.width) * size_of_datatype(l.dtype)
                   for l in self.leaves
                   if l.index == index and group in (None, l.group))


@dataclass(frozen=True)
class DecodeContext:
    """The serving context a training layer's decode layer is made for."""

    slots: int = 1
    max_seq: int = 1
    paged: bool = True
    block_size: int = 1
    blocks: int = 0         # of the global group's pool
    window_blocks: int = 0  # of the window group's
    impl: str = "auto"
    at_rest: DataType = DataType.DT_FLOAT
    prefill_chunk: int = 1


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
        # how a layer serves, where not as it is: (layer, DecodeContext) ->
        # (op type, params, the feeds the decode op reads beside the layer's
        # first input, by name; () = the layer's own inputs)
        decode_layer: Optional[Callable] = None,
        # a decode op: (params) -> DecodeState, allocated after `weights`,
        # and {name: index} of every leaf a declaration of it may hold
        state: Optional[Callable] = None,
        state_leaves: Optional[dict] = None,
        # whether the op acts on each row alone over the last axis, so
        # that rows gathered in front of it are the rows of its output:
        # True, or (params, in_shapes) -> bool where it is a fact of the
        # layer (a serving step runs such a tail of the graph on the rows
        # it samples from: Executor.build_decode_step)
        row_wise=False,
    ):
        self.op_type = op_type
        self.infer_shapes = infer_shapes
        self.forward = forward
        self.weights = weights or (lambda params, in_shapes: [])
        if state is not None:
            own = self.weights
            self.weights = lambda params, in_shapes: (
                own(params, in_shapes)
                + state(params).weight_specs(in_shapes[0][0]))
        self.flops = flops or _default_flops
        self.num_outputs = num_outputs
        self.decode_layer = decode_layer or (
            lambda layer, ctx: (layer.op_type, layer.params, ()))
        self.state = state
        self.state_leaves = state_leaves or {}
        self.row_wise = row_wise if callable(row_wise) else (
            lambda params, in_shapes: row_wise)


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
