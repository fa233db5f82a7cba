"""Elementwise operators.

Reference: src/ops/element_unary.cc/.cu (relu/sigmoid/tanh/elu/exp/sin/cos/
rsqrt/pow/scalar_*/identity/gelu, inplace support) and
src/ops/element_binary.cc + element_binary_kernels.cu (add/sub/mul/div/max/min
with cuDNN OpTensor broadcasting). On TPU these are single VPU-bound HLO ops
that XLA fuses into neighbors — the reference's FusedOp machinery
(src/ops/fused.cc) is unnecessary; fusion falls out of jit.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..fftype import OperatorType as OT
from .base import OpDef, register_op


@dataclass(frozen=True)
class ElementUnaryParams:
    op_type: OT
    inplace: bool = True  # kept for parity; XLA manages buffers itself
    scalar: float = 0.0


@dataclass(frozen=True)
class ElementBinaryParams:
    op_type: OT
    inplace_a: bool = False


_UNARY_FNS = {
    OT.OP_EXP: jnp.exp,
    OT.OP_LOG: jnp.log,
    OT.OP_SIN: jnp.sin,
    OT.OP_COS: jnp.cos,
    OT.OP_RELU: jax.nn.relu,
    OT.OP_IDENTITY: lambda x: x,
    OT.OP_GELU: lambda x: jax.nn.gelu(x, approximate=False),
    OT.OP_SIGMOID: jax.nn.sigmoid,
    OT.OP_TANH: jnp.tanh,
    OT.OP_ELU: jax.nn.elu,
    OT.OP_RSQRT: jax.lax.rsqrt,
    OT.OP_SQRT: jnp.sqrt,
    OT.OP_CEIL: jnp.ceil,
    OT.OP_ROUND: jnp.round,
    OT.OP_LOGICAL_NOT: jnp.logical_not,
    OT.OP_LEAKYRELU: jax.nn.leaky_relu,
}

_SCALAR_FNS = {
    OT.OP_SCALAR_MULTIPLY: lambda x, c: x * c,
    OT.OP_SCALAR_ADD: lambda x, c: x + c,
    OT.OP_SCALAR_SUB: lambda x, c: x - c,
    OT.OP_SCALAR_TRUE_DIV: lambda x, c: x / c,
    OT.OP_SCALAR_FLOOR_DIV: lambda x, c: jnp.floor_divide(x, c),
    OT.OP_POW: lambda x, c: jnp.power(x, c),
}

_BINARY_FNS = {
    OT.OP_EW_ADD: jnp.add,
    OT.OP_EW_SUB: jnp.subtract,
    OT.OP_EW_MUL: jnp.multiply,
    OT.OP_EW_DIV: jnp.divide,
    OT.OP_EW_MAX: jnp.maximum,
    OT.OP_EW_MIN: jnp.minimum,
    OT.OP_EW_EQUAL: jnp.equal,
    OT.OP_EW_GREATER: jnp.greater,
    OT.OP_EW_LESS: jnp.less,
}


def _unary_infer(params, in_shapes):
    return [in_shapes[0]]


def _unary_forward(params, inputs, weights, state, ctx):
    (x,) = inputs
    if params.op_type in _SCALAR_FNS:
        y = _SCALAR_FNS[params.op_type](x, params.scalar)
    else:
        y = _UNARY_FNS[params.op_type](x)
    return [y], state


def _binary_infer(params, in_shapes):
    a, b = in_shapes
    return [jnp.broadcast_shapes(tuple(a), tuple(b))]


def _binary_forward(params, inputs, weights, state, ctx):
    a, b = inputs
    return [_BINARY_FNS[params.op_type](a, b)], state


for _ot in list(_UNARY_FNS) + list(_SCALAR_FNS):
    register_op(OpDef(_ot, _unary_infer, _unary_forward, row_wise=True))

for _ot in _BINARY_FNS:
    register_op(OpDef(_ot, _binary_infer, _binary_forward))
