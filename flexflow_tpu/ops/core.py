"""Dense-compute operators: the MXU-bound core of the framework.

Reference kernels: src/ops/kernels/linear_kernels.cu (cuBLAS GEMM + cuDNN
activation), src/ops/conv_2d.cc + conv_2d_kernels.cu (cuDNN conv),
src/ops/pool_2d.cc, src/ops/batch_norm.cu, src/ops/layer_norm.cu (Welford),
src/ops/attention.cu (cudnnMultiHeadAttnForward), src/ops/embedding.cc,
src/ops/batch_matmul.cc, src/ops/kernels/softmax.cu, src/ops/dropout.cc.

TPU mapping: GEMMs/convs lower straight onto the MXU via jnp.dot/lax.conv
with bf16 accumulation policy controlled by FFConfig
(`allow_tensor_op_math_conversion` ≙ the reference's tensor-op math flag);
normalizations and activations are VPU ops that XLA fuses into the adjacent
GEMM's epilogue. Layouts: user-facing shapes keep the reference's NCHW
convention; XLA repacks internally for the TPU's native tiling.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from ..fftype import ActiMode, AggrMode, DataType, OperatorType as OT, PoolType, RegularizerMode
from .base import OpDef, WeightSpec, matmul_cast, register_op


def apply_activation(x, activation: ActiMode):
    if activation == ActiMode.AC_MODE_NONE:
        return x
    if activation == ActiMode.AC_MODE_RELU:
        return jax.nn.relu(x)
    if activation == ActiMode.AC_MODE_SIGMOID:
        return jax.nn.sigmoid(x)
    if activation == ActiMode.AC_MODE_TANH:
        return jnp.tanh(x)
    if activation == ActiMode.AC_MODE_GELU:
        return jax.nn.gelu(x, approximate=False)
    raise ValueError(f"unknown activation {activation}")


# ---------------------------------------------------------------- Linear

@dataclass(frozen=True)
class LinearParams:
    out_channels: int
    use_bias: bool = True
    activation: ActiMode = ActiMode.AC_MODE_NONE
    data_type: DataType = DataType.DT_FLOAT
    kernel_reg_type: RegularizerMode = RegularizerMode.REG_MODE_NONE
    kernel_reg_lambda: float = 0.0
    # the kernel lies (out, in) and is applied as x @ kernel^T: the layout
    # of an embedding's table, which a head tied to it reads as it lies
    # (FFModel.dense(shared_op=<the embedding>)); False = (in, out)
    kernel_transposed: bool = False
    # the output stays in the matmul's float32 accumulator where the input
    # is narrower (a head whose logits are float32 under bf16 weights)
    float32_out: bool = False


def _linear_infer(p: LinearParams, in_shapes):
    (x,) = in_shapes
    return [tuple(x[:-1]) + (p.out_channels,)]


def _linear_weights(p: LinearParams, in_shapes):
    in_dim = in_shapes[0][-1]
    shape = ((p.out_channels, in_dim) if p.kernel_transposed
             else (in_dim, p.out_channels))
    ws = [WeightSpec("kernel", shape, p.data_type, "glorot_uniform")]
    if p.use_bias:
        ws.append(WeightSpec("bias", (p.out_channels,), p.data_type, "zeros"))
    return ws


def _linear_forward(p: LinearParams, inputs, weights, state, ctx):
    (x,) = inputs
    xm, km = matmul_cast(ctx, x, weights["kernel"])
    if p.kernel_transposed:
        y = jax.lax.dot_general(
            xm, km, (((xm.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        y = jnp.dot(xm, km, preferred_element_type=jnp.float32)
    if not p.float32_out:
        y = y.astype(x.dtype)
    if p.use_bias:
        y = y + weights["bias"]
    return [apply_activation(y, p.activation)], state


def _linear_flops(p: LinearParams, in_shapes, out_shapes):
    x = in_shapes[0]
    return 2.0 * math.prod(x) * p.out_channels


register_op(OpDef(OT.OP_LINEAR, _linear_infer, _linear_forward, _linear_weights,
                  _linear_flops, row_wise=True))


# ---------------------------------------------------------------- Conv2D

@dataclass(frozen=True)
class Conv2DParams:
    out_channels: int
    kernel_h: int
    kernel_w: int
    stride_h: int
    stride_w: int
    padding_h: int
    padding_w: int
    groups: int = 1
    use_bias: bool = True
    activation: ActiMode = ActiMode.AC_MODE_NONE


def _conv2d_out_hw(p: Conv2DParams, h, w):
    oh = (h + 2 * p.padding_h - p.kernel_h) // p.stride_h + 1
    ow = (w + 2 * p.padding_w - p.kernel_w) // p.stride_w + 1
    return oh, ow


def _conv2d_infer(p: Conv2DParams, in_shapes):
    n, c, h, w = in_shapes[0]
    oh, ow = _conv2d_out_hw(p, h, w)
    return [(n, p.out_channels, oh, ow)]


def _conv2d_weights(p: Conv2DParams, in_shapes):
    c = in_shapes[0][1]
    ws = [
        WeightSpec(
            "kernel",
            (p.out_channels, c // p.groups, p.kernel_h, p.kernel_w),
            DataType.DT_FLOAT,
            "glorot_uniform",
        )
    ]
    if p.use_bias:
        ws.append(WeightSpec("bias", (p.out_channels,), DataType.DT_FLOAT, "zeros"))
    return ws


def _conv2d_forward(p: Conv2DParams, inputs, weights, state, ctx):
    (x,) = inputs
    x = matmul_cast(ctx, x)
    # same-dtype conv without preferred_element_type: lax.conv's transpose
    # (VJP) requires matching operand dtypes, and the MXU accumulates fp32
    # internally for bf16 convs regardless of the output element type
    y = jax.lax.conv_general_dilated(
        x,
        weights["kernel"].astype(x.dtype),
        window_strides=(p.stride_h, p.stride_w),
        padding=[(p.padding_h, p.padding_h), (p.padding_w, p.padding_w)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=p.groups,
    ).astype(inputs[0].dtype)
    if p.use_bias:
        y = y + weights["bias"][None, :, None, None].astype(y.dtype)
    return [apply_activation(y, p.activation)], state


def _conv2d_flops(p: Conv2DParams, in_shapes, out_shapes):
    n, c, h, w = in_shapes[0]
    _, oc, oh, ow = out_shapes[0]
    return 2.0 * n * oc * oh * ow * (c // p.groups) * p.kernel_h * p.kernel_w


register_op(OpDef(OT.OP_CONV2D, _conv2d_infer, _conv2d_forward, _conv2d_weights, _conv2d_flops))


# ---------------------------------------------------------------- Pool2D

@dataclass(frozen=True)
class Pool2DParams:
    kernel_h: int
    kernel_w: int
    stride_h: int
    stride_w: int
    padding_h: int
    padding_w: int
    pool_type: PoolType = PoolType.POOL_MAX
    activation: ActiMode = ActiMode.AC_MODE_NONE


def _pool2d_infer(p: Pool2DParams, in_shapes):
    n, c, h, w = in_shapes[0]
    oh = (h + 2 * p.padding_h - p.kernel_h) // p.stride_h + 1
    ow = (w + 2 * p.padding_w - p.kernel_w) // p.stride_w + 1
    return [(n, c, oh, ow)]


def _pool2d_forward(p: Pool2DParams, inputs, weights, state, ctx):
    (x,) = inputs
    pads = ((0, 0), (0, 0), (p.padding_h, p.padding_h), (p.padding_w, p.padding_w))
    dims = (1, 1, p.kernel_h, p.kernel_w)
    strides = (1, 1, p.stride_h, p.stride_w)
    if p.pool_type == PoolType.POOL_MAX:
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        y = jax.lax.reduce_window(x, init, jax.lax.max, dims, strides, pads)
    else:
        summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, dims, strides, pads)
        # cuDNN CUDNN_POOLING_AVERAGE_COUNT_INCLUDE_PADDING semantics
        y = summed / (p.kernel_h * p.kernel_w)
    return [apply_activation(y, p.activation)], state


register_op(OpDef(OT.OP_POOL2D, _pool2d_infer, _pool2d_forward))


# ---------------------------------------------------------------- Flat

def _flat_infer(p, in_shapes):
    n = in_shapes[0][0]
    return [(n, math.prod(in_shapes[0][1:]))]


def _flat_forward(p, inputs, weights, state, ctx):
    (x,) = inputs
    return [x.reshape(x.shape[0], -1)], state


register_op(OpDef(OT.OP_FLAT, _flat_infer, _flat_forward))


# ---------------------------------------------------------------- BatchNorm

@dataclass(frozen=True)
class BatchNormParams:
    relu: bool = True
    momentum: float = 0.1
    eps: float = 1e-5


def _bn_infer(p, in_shapes):
    return [in_shapes[0]]


def _bn_weights(p: BatchNormParams, in_shapes):
    c = in_shapes[0][1]
    return [
        WeightSpec("scale", (c,), DataType.DT_FLOAT, "ones"),
        WeightSpec("bias", (c,), DataType.DT_FLOAT, "zeros"),
        WeightSpec("running_mean", (c,), DataType.DT_FLOAT, "zeros", trainable=False),
        WeightSpec("running_var", (c,), DataType.DT_FLOAT, "ones", trainable=False),
    ]


def _bn_forward(p: BatchNormParams, inputs, weights, state, ctx):
    (x,) = inputs
    axes = (0, 2, 3)
    # statistics always in fp32 (mixed-precision policy: bf16 mean/var
    # accumulation loses too many mantissa bits)
    xf = x.astype(jnp.float32)
    if ctx.training:
        mean = jnp.mean(xf, axes)
        var = jnp.var(xf, axes)
        state = dict(state or {})
        state["running_mean"] = (
            (1 - p.momentum) * weights["running_mean"].astype(jnp.float32)
            + p.momentum * mean
        )
        state["running_var"] = (
            (1 - p.momentum) * weights["running_var"].astype(jnp.float32)
            + p.momentum * var
        )
    else:
        mean = weights["running_mean"].astype(jnp.float32)
        var = weights["running_var"].astype(jnp.float32)
    inv = jax.lax.rsqrt(var + p.eps)
    y = (xf - mean[None, :, None, None]) * inv[None, :, None, None]
    y = y.astype(x.dtype)
    y = y * weights["scale"][None, :, None, None] + weights["bias"][None, :, None, None]
    if p.relu:
        y = jax.nn.relu(y)
    return [y], state


register_op(OpDef(OT.OP_BATCHNORM, _bn_infer, _bn_forward, _bn_weights))


# ---------------------------------------------------------------- LayerNorm

@dataclass(frozen=True)
class LayerNormParams:
    axes: tuple[int, ...]
    elementwise_affine: bool = True
    eps: float = 1e-5
    # False: a learned scale and no bias (the affine is the scale alone)
    bias: bool = True


def _ln_infer(p, in_shapes):
    return [in_shapes[0]]


def _ln_weights(p: LayerNormParams, in_shapes):
    if not p.elementwise_affine:
        return []
    shape = tuple(in_shapes[0][a] for a in p.axes)
    return [WeightSpec("scale", shape, DataType.DT_FLOAT, "ones"),
            *([WeightSpec("bias", shape, DataType.DT_FLOAT, "zeros")]
              if p.bias else [])]


def _ln_forward(p: LayerNormParams, inputs, weights, state, ctx):
    (x,) = inputs
    axes = tuple(a % x.ndim for a in p.axes)
    if p.elementwise_affine:
        scale = weights["scale"]
        # without a bias the kernel adds zeros
        bias = weights["bias"] if p.bias else jnp.zeros_like(scale)
        # fused Pallas kernel for the tiling-friendly common case (one
        # HBM pass instead of XLA's off-roofline convert+reduce fusion;
        # kernels/layer_norm.py)
        from ..kernels.layer_norm import fused_layer_norm_or_none

        fused = fused_layer_norm_or_none(
            x, scale, bias, axes, p.eps,
            mesh=ctx.mesh, spec=ctx.out_spec)
        if fused is not None:
            return [fused], state
    xf = x.astype(jnp.float32)  # fp32 statistics under mixed precision
    mean = jnp.mean(xf, axes, keepdims=True)
    var = jnp.var(xf, axes, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + p.eps)
    if p.elementwise_affine:
        # affine still in f32 (matching the fused kernel's semantics; a
        # bf16·f32 product would also silently promote activations), one
        # final cast to the activation dtype
        bshape = [x.shape[a] if a in axes else 1 for a in range(x.ndim)]
        y = y * scale.reshape(bshape)
        if p.bias:
            y = y + bias.reshape(bshape)
    return [y.astype(x.dtype)], state


def _ln_row_wise(p: LayerNormParams, in_shapes):
    rank = len(in_shapes[0])
    return tuple(a % rank for a in p.axes) == (rank - 1,)


register_op(OpDef(OT.OP_LAYERNORM, _ln_infer, _ln_forward, _ln_weights,
                  row_wise=_ln_row_wise))


# ---------------------------------------------------------------- RMSNorm

@dataclass(frozen=True)
class RMSNormParams:
    eps: float = 1e-5
    # the learned `scale` is g of a scale of 1 + g, and starts at zeros
    unit_offset: bool = False
    # the output in the dtype the scale comes in (the compute dtype) where
    # the input is wider: the norm of a float32 residual stream whose
    # matmuls are bf16
    narrow_out: bool = False


def rms_norm(x, scale, eps: float, out_dtype=None):
    """x * rsqrt(mean(x^2) + eps) * scale over the last dim: fp32
    statistics and affine, one cast back to the activation dtype (or to
    `out_dtype`)."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(out_dtype or x.dtype)


def _rms_weights(p: RMSNormParams, in_shapes):
    return [WeightSpec("scale", (in_shapes[0][-1],), DataType.DT_FLOAT,
                       "zeros" if p.unit_offset else "ones")]


def _rms_forward(p: RMSNormParams, inputs, weights, state, ctx):
    scale = weights["scale"]
    return [rms_norm(
        inputs[0],
        scale.astype(jnp.float32) + 1.0 if p.unit_offset else scale, p.eps,
        scale.dtype if p.narrow_out else None)], state


register_op(OpDef(OT.OP_RMSNORM, _ln_infer, _rms_forward, _rms_weights,
                  row_wise=True))


# ---------------------------------------------------------------- Softmax

@dataclass(frozen=True)
class SoftmaxParams:
    dim: int = -1


def _softmax_infer(p, in_shapes):
    return [in_shapes[0]]


def _softmax_forward(p: SoftmaxParams, inputs, weights, state, ctx):
    (x,) = inputs
    # fp32 exponentials/normalization, output back in the activation dtype
    y = jax.nn.softmax(x.astype(jnp.float32), axis=p.dim).astype(x.dtype)
    return [y], state


register_op(OpDef(OT.OP_SOFTMAX, _softmax_infer, _softmax_forward))


# ---------------------------------------------------------------- Dropout

@dataclass(frozen=True)
class DropoutParams:
    rate: float
    seed: int = 0


def _dropout_infer(p, in_shapes):
    return [in_shapes[0]]


def _dropout_forward(p: DropoutParams, inputs, weights, state, ctx):
    (x,) = inputs
    if not ctx.training or p.rate <= 0.0:
        return [x], state
    keep = 1.0 - p.rate
    mask = jax.random.bernoulli(ctx.rng, keep, x.shape)
    return [jnp.where(mask, x / keep, 0.0).astype(x.dtype)], state


# row-wise as a serving step runs it: the identity outside training
register_op(OpDef(OT.OP_DROPOUT, _dropout_infer, _dropout_forward,
                  row_wise=True))


# ---------------------------------------------------------------- BatchMatmul

@dataclass(frozen=True)
class BatchMatmulParams:
    a_seq_length_dim: int = -1
    b_seq_length_dim: int = -1


def _bmm_infer(p, in_shapes):
    a, b = in_shapes
    if a[:-2] != b[:-2]:
        raise ValueError(f"batch dims mismatch: {a} vs {b}")
    if a[-1] != b[-2]:
        raise ValueError(f"contraction mismatch: {a} vs {b}")
    return [tuple(a[:-2]) + (a[-2], b[-1])]


def _bmm_forward(p: BatchMatmulParams, inputs, weights, state, ctx):
    a, b = inputs
    if ctx.seq_length >= 0:
        # truncated-sequence batches (FFIterationConfig::seq_length,
        # reference include/flexflow/config.h:162-167)
        if p.a_seq_length_dim >= 0:
            a = jax.lax.slice_in_dim(a, 0, ctx.seq_length, axis=p.a_seq_length_dim)
        if p.b_seq_length_dim >= 0:
            b = jax.lax.slice_in_dim(b, 0, ctx.seq_length, axis=p.b_seq_length_dim)
    am, bm = matmul_cast(ctx, a, b)
    y = jnp.matmul(am, bm, preferred_element_type=jnp.float32).astype(a.dtype)
    return [y], state


def _bmm_flops(p, in_shapes, out_shapes):
    a, b = in_shapes
    return 2.0 * math.prod(out_shapes[0]) * a[-1]


register_op(OpDef(OT.OP_BATCHMATMUL, _bmm_infer, _bmm_forward, flops=_bmm_flops))


# ---------------------------------------------------------------- Embedding

@dataclass(frozen=True)
class EmbeddingParams:
    num_entries: int
    out_channels: int
    aggr: AggrMode = AggrMode.AGGR_MODE_NONE
    data_type: DataType = DataType.DT_FLOAT
    # the rows come out float32 whatever the table rests in: the start of
    # a residual stream held in float32 under bf16 weights
    float32_out: bool = False


def _embedding_infer(p: EmbeddingParams, in_shapes):
    x = in_shapes[0]
    if p.aggr == AggrMode.AGGR_MODE_NONE:
        return [tuple(x) + (p.out_channels,)]
    return [tuple(x[:-1]) + (p.out_channels,)]


def _embedding_weights(p: EmbeddingParams, in_shapes):
    return [
        WeightSpec(
            "kernel", (p.num_entries, p.out_channels), p.data_type, "glorot_uniform"
        )
    ]


def _embedding_forward(p: EmbeddingParams, inputs, weights, state, ctx):
    (ids,) = inputs
    table = weights["kernel"]
    # gather rides the VPU; for giant tables sharded over the model axis GSPMD
    # turns this into an all-to-all — same role as the reference's custom
    # scatter/gather kernels (src/ops/kernels/embedding_kernels.cu)
    emb = jnp.take(table, ids.astype(jnp.int32), axis=0)
    if p.aggr == AggrMode.AGGR_MODE_SUM:
        emb = jnp.sum(emb, axis=-2)
    elif p.aggr == AggrMode.AGGR_MODE_AVG:
        emb = jnp.mean(emb, axis=-2)
    if p.float32_out:
        emb = emb.astype(jnp.float32)
    return [emb], state


register_op(
    OpDef(OT.OP_EMBEDDING, _embedding_infer, _embedding_forward, _embedding_weights)
)
