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
window; the taps' sum is float32, as ops/recurrent.causal_conv takes it;
the backward is autodiff's.

There is no decode op: what a slot would keep is the last `taps - 1` rows
of u (ops/recurrent.conv_window / next_tail carry such a tail for the
delta rule and the state-space layer), and `decode_layer` refuses by the
layer's name until that op exists.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import ClassVar

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..fftype import DataType, OperatorType as OT
from .attention import proj
from .base import OpDef, WeightSpec, matmul_cast, register_op
from .recurrent import causal_conv, infer_shapes


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
        """(B, C, x'), each (.., channels) in x's dtype."""
        with jax.named_scope("sconv.proj"):
            xm, wm = matmul_cast(ctx, x, weights["w_in"].astype(x.dtype))
            bcx = jnp.einsum("...d,dge->...ge", xm, wm,
                             preferred_element_type=jnp.float32
                             ).astype(x.dtype)
            return bcx[..., 0, :], bcx[..., 1, :], bcx[..., 2, :]

    def conv(self, weights, u):
        """c (rows, tokens, channels) float32 of u, every row from an empty
        window."""
        with jax.named_scope("sconv.conv"):
            window = jnp.pad(u, ((0, 0), (self.conv_kernel - 1, 0), (0, 0)))
            return causal_conv(weights["conv"], window, u.shape[1],
                               activation=None)

    def output(self, ctx, weights, c, gate):
        """(gate * c) W_out in gate's dtype."""
        with jax.named_scope("sconv.out"):
            y = (gate.astype(jnp.float32) * c).astype(gate.dtype)
            return proj(ctx, y, weights["w_out"], None)

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
    B, C, x = f.project(ctx, weights, inputs[0])
    c = f.conv(weights, B * x)
    return [f.output(ctx, weights, c, C)], state


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
