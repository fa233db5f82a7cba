"""Shape/data-movement operators.

Reference: src/ops/{concat,split,reshape,transpose,flat,reverse,cast,gather,
reduce,mean,topk}.cc with custom CUDA kernels. On TPU every one of these is a
layout/copy HLO that XLA either elides (bitcast) or fuses; none need custom
kernels. Semantics (axis conventions, keepdims, torch.gather indexing) follow
the reference's Python API which presents NumPy dim order.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..fftype import DataType, OperatorType as OT, dtype_to_jnp
from .base import OpDef, register_op


# ---------------------------------------------------------------- Concat

@dataclass(frozen=True)
class ConcatParams:
    axis: int
    n: int = 2


def _concat_infer(p: ConcatParams, in_shapes):
    base = list(in_shapes[0])
    ax = p.axis % len(base)
    base[ax] = sum(s[ax] for s in in_shapes)
    return [tuple(base)]


def _concat_forward(p, inputs, weights, state, ctx):
    return [jnp.concatenate(inputs, axis=p.axis)], state


register_op(OpDef(OT.OP_CONCAT, _concat_infer, _concat_forward))


# ---------------------------------------------------------------- Split

@dataclass(frozen=True)
class SplitParams:
    sizes: tuple[int, ...]
    axis: int


def _split_infer(p: SplitParams, in_shapes):
    base = in_shapes[0]
    ax = p.axis % len(base)
    outs = []
    for sz in p.sizes:
        s = list(base)
        s[ax] = sz
        outs.append(tuple(s))
    return outs


def _split_forward(p: SplitParams, inputs, weights, state, ctx):
    (x,) = inputs
    ax = p.axis % x.ndim
    offsets = [0]
    for sz in p.sizes:
        offsets.append(offsets[-1] + sz)
    outs = [
        jax.lax.slice_in_dim(x, offsets[i], offsets[i + 1], axis=ax)
        for i in range(len(p.sizes))
    ]
    return outs, state


register_op(
    OpDef(OT.OP_SPLIT, _split_infer, _split_forward, num_outputs=-1)
)


# ---------------------------------------------------------------- Reshape

@dataclass(frozen=True)
class ReshapeParams:
    shape: tuple[int, ...]


def _reshape_infer(p: ReshapeParams, in_shapes):
    n_in = math.prod(in_shapes[0])
    if math.prod(p.shape) != n_in:
        raise ValueError(f"cannot reshape {in_shapes[0]} to {p.shape}")
    return [tuple(p.shape)]


def _reshape_forward(p, inputs, weights, state, ctx):
    return [inputs[0].reshape(p.shape)], state


register_op(OpDef(OT.OP_RESHAPE, _reshape_infer, _reshape_forward))


# ---------------------------------------------------------------- Transpose

@dataclass(frozen=True)
class TransposeParams:
    perm: tuple[int, ...]


def _transpose_infer(p: TransposeParams, in_shapes):
    x = in_shapes[0]
    return [tuple(x[i] for i in p.perm)]


def _transpose_forward(p, inputs, weights, state, ctx):
    return [jnp.transpose(inputs[0], p.perm)], state


register_op(OpDef(OT.OP_TRANSPOSE, _transpose_infer, _transpose_forward))


# ---------------------------------------------------------------- Reverse

@dataclass(frozen=True)
class ReverseParams:
    axis: int


def _reverse_infer(p, in_shapes):
    return [in_shapes[0]]


def _reverse_forward(p, inputs, weights, state, ctx):
    return [jnp.flip(inputs[0], axis=p.axis)], state


register_op(OpDef(OT.OP_REVERSE, _reverse_infer, _reverse_forward))


# ---------------------------------------------------------------- Cast

@dataclass(frozen=True)
class CastParams:
    dtype: DataType


def _cast_infer(p, in_shapes):
    return [in_shapes[0]]


def _cast_forward(p: CastParams, inputs, weights, state, ctx):
    return [inputs[0].astype(dtype_to_jnp(p.dtype))], state


register_op(OpDef(OT.OP_CAST, _cast_infer, _cast_forward, row_wise=True))


# ---------------------------------------------------------------- Gather

@dataclass(frozen=True)
class GatherParams:
    dim: int


def _gather_infer(p: GatherParams, in_shapes):
    return [in_shapes[1]]  # index shape (torch.gather semantics)


def _gather_forward(p: GatherParams, inputs, weights, state, ctx):
    x, index = inputs
    return [jnp.take_along_axis(x, index.astype(jnp.int32), axis=p.dim)], state


register_op(OpDef(OT.OP_GATHER, _gather_infer, _gather_forward))


# ---------------------------------------------------------------- Reduce / Mean

@dataclass(frozen=True)
class ReduceParams:
    op_type: OT
    axes: tuple[int, ...]
    keepdims: bool = False


_REDUCE_FNS = {
    OT.OP_REDUCE_SUM: jnp.sum,
    OT.OP_REDUCE_MEAN: jnp.mean,
    OT.OP_REDUCE_MAX: jnp.max,
    OT.OP_REDUCE_MIN: jnp.min,
    OT.OP_REDUCE_PROD: jnp.prod,
    OT.OP_MEAN: jnp.mean,
}


def _reduce_infer(p: ReduceParams, in_shapes):
    x = list(in_shapes[0])
    axes = sorted(a % len(x) for a in p.axes)
    if p.keepdims:
        for a in axes:
            x[a] = 1
        return [tuple(x)]
    return [tuple(s for i, s in enumerate(x) if i not in axes)]


def _reduce_forward(p: ReduceParams, inputs, weights, state, ctx):
    fn = _REDUCE_FNS[p.op_type]
    return [fn(inputs[0], axis=tuple(p.axes), keepdims=p.keepdims)], state


for _ot in _REDUCE_FNS:
    register_op(OpDef(_ot, _reduce_infer, _reduce_forward))


# ---------------------------------------------------------------- TopK

@dataclass(frozen=True)
class TopKParams:
    k: int
    sorted: bool = True


def _topk_infer(p: TopKParams, in_shapes):
    x = list(in_shapes[0])
    x[-1] = p.k
    return [tuple(x), tuple(x)]


def _topk_forward(p: TopKParams, inputs, weights, state, ctx):
    values, indices = jax.lax.top_k(inputs[0], p.k)
    return [values, indices], state


register_op(OpDef(OT.OP_TOPK, _topk_infer, _topk_forward, num_outputs=2))
