"""The gated short convolution (LFM2's `conv` mixer, transformers'
`Lfm2ShortConv`), training-shaped. The equations are written out in
models/lfm2_moe_reference.py:

    [B | C | x] = n W_in        (hidden -> 3 x hidden, no bias)
    u = B * x
    c[t] = sum_j w[j] * u[t - (taps - 1) + j]   (causal, depthwise, a
                                 channel each, NO activation and no bias)
    y = (C * c) W_out           (hidden -> hidden)

`ShortConvFrontEnd` owns the weights and their names. `w_in` lies (hidden,
3, channels): B, C and x of one channel are one index of the last axis, so
a plan that splits the channels splits all three alike (`w_in` by column,
the taps by channel, `w_out` by row; the row-parallel partial sums are the
plan's psum: `channel_parallel`). Every sequence starts from an empty
window; the taps' sum is float32, as ops/recurrent.causal_conv takes it.

The in-projection hands B, C, x over as one array `bcx` (3, rows, tokens,
channels), the layout XLA picks for it anyway. Where `_kernel_plan` lets
them, the part between the two projections (u, c and the gate, scope
`sconv.conv`) is one Pallas kernel forward and one backward
(kernels/short_conv.py: B, C, x read once and y written once; the backward
reads them and dy, makes u and c again in VMEM and writes d_bcx as dW_in and
dX_in take it, and y again for dW_out), joined with the out-projection by
one `custom_vjp` (`_through_kernels`): nothing of (tokens, channels) in
float32 goes to HBM, and nothing is kept for the backward but `bcx`, the
taps and `w_out`. Who takes them is read off the call: a TPU backend,
channels (a shard's) a multiple of 128, tokens of 8, at most 9 taps, bf16 or
float32; on a mesh of several devices each shard of the plan runs them on
its rows and channels (`per_shard`: rows and channels are independent, and
the row-parallel psum stays the plan's, after `w_out`). Every other call
keeps the jnp form over `causal_conv` (`ShortConvFrontEnd.conv`), whose
backward is autodiff's: the CPU's path and the kernels' oracle, said with a
`KernelFallbackWarning` on a TPU.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import ClassVar

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..fftype import DataType, OperatorType as OT
from ..kernels import short_conv as kernel
from ..kernels.dispatch import (
    per_shard, shards_of, spec_entries, warn_reference,
)
from .attention import proj
from .base import OpDef, WeightSpec, matmul_cast, register_op
from .recurrent import causal_conv, infer_shapes


def _backend() -> str:
    return jax.default_backend()


def _kernel_plan(ctx, shape, taps: int, dtype):
    """((rows' mesh axis, channels' mesh axis), None) where the kernels take
    `bcx` of `shape`, else (None, why not). The axes are the plan's: rows
    as the output is placed, channels as the taps are."""
    mesh = ctx.mesh
    _, rows, tokens, channels = shape
    row_ax = spec_entries(ctx.out_spec, 1)[0]
    ch_ax = spec_entries((ctx.weight_axes or {}).get("conv"), 2)[1]
    if rows % shards_of(mesh, row_ax):
        row_ax = None
    if channels % shards_of(mesh, ch_ax):
        ch_ax = None
    if _backend() != "tpu":
        return None, f"backend {_backend()!r} is no TPU"
    if mesh is not None and mesh.size > 1 and row_ax is None and ch_ax is None:
        return None, (f"neither rows nor channels are split over the mesh "
                      f"{dict(mesh.shape)}")
    why = kernel.refusal(tokens, channels // shards_of(mesh, ch_ax), taps,
                         dtype)
    return (None, why) if why else ((row_ax, ch_ax), None)


@dataclass(frozen=True)
class ShortConvFrontEnd:
    embed_dim: int          # the model's hidden size, and the channels
    conv_kernel: int = 3    # taps (`conv_L_cache`)

    kernels: ClassVar[tuple] = ("w_in", "w_out")

    def weight_specs(self, in_dim: int):
        f, E = DataType.DT_FLOAT, self.embed_dim
        return [
            WeightSpec("w_in", (in_dim, 3, E), f, "normal"),
            # taps of the causal depthwise convolution, a channel each
            WeightSpec("conv", (self.conv_kernel, E), f, "uniform"),
            WeightSpec("w_out", (E, E), f, "normal"),
        ]

    def initializers(self, kernel_initializer=None) -> dict:
        """Taps uniform in +-conv_kernel^-0.5 (torch's Conv1d draw for a
        depthwise kernel); matrices by `kernel_initializer` where one is
        given."""
        from ..initializer import UniformInitializer

        k = self.conv_kernel ** -0.5
        inits = {"conv": UniformInitializer(min_val=-k, max_val=k)}
        if kernel_initializer is not None:
            inits.update(dict.fromkeys(self.kernels, kernel_initializer))
        return inits

    def project(self, ctx, weights, x):
        """`bcx` (3, .., channels) in x's dtype: B, C and x'."""
        with jax.named_scope("sconv.proj"):
            xm, wm = matmul_cast(ctx, x, weights["w_in"].astype(x.dtype))
            return jnp.einsum("...d,dge->g...e", xm, wm,
                              preferred_element_type=jnp.float32
                              ).astype(x.dtype)

    def conv(self, weights, bcx):
        """C * c (rows, tokens, channels) in bcx's dtype, c the taps' sum
        over u = B * x' in float32, every row from an empty window."""
        with jax.named_scope("sconv.conv"):
            B, C, x = bcx
            u = B * x
            window = jnp.pad(u, ((0, 0), (self.conv_kernel - 1, 0), (0, 0)))
            c = causal_conv(weights["conv"], window, u.shape[1],
                            activation=None)
            return (C.astype(jnp.float32) * c).astype(bcx.dtype)

    def output(self, ctx, w_out, y):
        """y W_out."""
        with jax.named_scope("sconv.out"):
            return proj(ctx, y, w_out, None)

    def linear_flops(self, tokens: int, in_dim: int) -> float:
        E = self.embed_dim
        return 2.0 * tokens * (in_dim * 3 * E + E * self.conv_kernel + E * E)

    def channel_parallel_ok(self, degree: int) -> bool:
        return self.embed_dim % degree == 0

    def channel_parallel(self, axis):
        """(weight name, PartitionSpec) of the channels split over mesh
        axis `axis`: `w_in` column-parallel, the taps by channel, `w_out`
        row-parallel (its partial sums are the caller's psum)."""
        return (("w_in", PartitionSpec(None, None, axis)),
                ("conv", PartitionSpec(None, axis)),
                ("w_out", PartitionSpec(axis, None)))


@dataclass(frozen=True)
class ShortConvParams:
    front: ShortConvFrontEnd

    embed_dim = property(lambda self: self.front.embed_dim)


def _sconv_weights(p: ShortConvParams, in_shapes):
    return p.front.weight_specs(in_shapes[0][-1])


def _sconv_forward(p: ShortConvParams, inputs, weights, state, ctx):
    f = p.front
    bcx = f.project(ctx, weights, inputs[0])
    axes, why = _kernel_plan(ctx, bcx.shape, f.conv_kernel, bcx.dtype)
    if axes is None:
        warn_reference("short_conv", bcx.shape, why)
        y = f.output(ctx, weights["w_out"], f.conv(weights, bcx))
    else:
        y = _through_kernels(f, ctx, axes, bcx, weights["conv"],
                             weights["w_out"])
    return [y], state


def _through_kernels(f, ctx, axes, bcx, taps, w_out):
    """(C * c) W_out with the middle in the kernels, each shard of the plan
    on its rows and channels, and NOTHING kept for the backward but the
    three operands: the backward kernel writes y again beside d_bcx (c is
    in VMEM there anyway) for dW_out. A y kept from the forward would be an
    array XLA cannot make again (a custom call's result) where the jnp form
    kept none."""
    row_ax, ch_ax = axes
    y_spec = PartitionSpec(row_ax, None, ch_ax)
    b_spec, t_spec = PartitionSpec(None, *y_spec), PartitionSpec(None, ch_ax)

    @jax.custom_vjp
    def layer(bcx, taps, w_out):
        with jax.named_scope("sconv.conv"):
            y = per_shard(kernel.forward, ctx.mesh, (b_spec, t_spec),
                          y_spec)(bcx, taps)
        return f.output(ctx, w_out, y)

    def layer_fwd(bcx, taps, w_out):
        return layer(bcx, taps, w_out), (bcx, taps, w_out)

    def layer_bwd(res, d_out):
        bcx, taps, w_out = res
        (dy,) = jax.linear_transpose(
            lambda y: f.output(ctx, w_out, y),
            jax.ShapeDtypeStruct(bcx.shape[1:], bcx.dtype))(d_out)
        with jax.named_scope("sconv.conv"):
            # a shard's d_taps is the sum over its rows: (1, taps, channels)
            d_bcx, d_taps, y = per_shard(
                kernel.backward, ctx.mesh, (b_spec, t_spec, y_spec),
                (b_spec, y_spec, y_spec))(bcx, taps, dy)
        (dw,) = jax.linear_transpose(lambda w: f.output(ctx, w, y), w_out)(
            d_out)
        return d_bcx, d_taps.sum(axis=0).astype(taps.dtype), dw

    layer.defvjp(layer_fwd, layer_bwd)
    return layer(bcx, taps, w_out)


def _sconv_flops(p, in_shapes, out_shapes):
    rows, tokens, d = in_shapes[0]
    return p.front.linear_flops(rows * tokens, d)


def _sconv_decode_layer(layer, ctx):
    raise NotImplementedError(
        f"{layer.name}: a gated short convolution has no decode op yet (a "
        f"slot's state would be the last {layer.params.front.conv_kernel - 1}"
        f" rows of B * x): this graph trains and evaluates, it is not "
        f"served")


register_op(OpDef(OT.OP_SHORT_CONV, infer_shapes, _sconv_forward,
                  _sconv_weights, _sconv_flops,
                  decode_layer=_sconv_decode_layer))
