"""Gated delta-rule linear attention (the recurrent layers of
Solar-Open2: Kimi-style "KDA", a decay a key channel, negative
eigenvalues allowed), as two ops over one front end. The equations are
written out in models/solar_open2_reference.py.

`DeltaFrontEnd` owns what both share: the weights and their names, the
q / k / v projections, the causal depthwise convolution with SiLU, the
l2-normalised heads, the decay alpha and the step beta, the gated RMSNorm
of the output and the output projection. What a sequence leaves behind is
of fixed size: a state S (heads, d, d) float32 and the convolution's last
`conv_kernel - 1` inputs.

- OP_GATED_DELTA_ATTENTION, the training-shaped op on (batch, seq,
  hidden): every sequence starts from the zero state, the recurrence is a
  `lax.scan` over its tokens (kernels/delta_rule.delta_rule_reference),
  plain jnp, differentiable by autodiff.
- OP_GATED_DELTA_ATTENTION_DECODE, the decode op: state leaves `state_s`
  (slots, heads, d, d) float32 and `state_conv` (slots, conv_kernel - 1,
  3 heads d), indexed by SLOT, not by page. Its rows follow the serving
  engine's two layouts of a step (ops/recurrent.decode_rows, which the
  selective state-space layer shares: the rectangle, or the slots' rows
  and then one chunk's rows run in order from the slot `state_slot`
  names). A dead token leaves the state as it is. A row whose first live
  token is at position 0 starts from the zero state and an empty
  convolution window, so a slot given to a new request is reset inside
  the step's program. One token a row runs the Pallas kernel
  (kernels/delta_rule.py) where its gate allows, state aliased in place;
  so does a chunk, whose state stays in VMEM over its tokens (timed on
  the chip against the `lax.scan`: PERF.md section 6).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..fftype import DataType, OperatorType as OT
from .attention import proj
from .base import BY_SLOT, DecodeState, OpDef, WeightSpec, register_op
from .core import rms_norm
from .recurrent import (
    causal_conv, conv_window, decode_rows, infer_shapes, next_tail,
    slot_state,
)


@dataclass(frozen=True)
class DeltaFrontEnd:
    embed_dim: int
    num_heads: int
    head_dim: int
    conv_kernel: int = 4
    # width of the low-rank pairs that make the decay and the output gate
    # (`kda_use_full_proj: false`); 0 = head_dim
    low_rank: int = 0
    # beta in (0, 2): the state's transition may have negative
    # eigenvalues (`kda_allow_neg_eigval`)
    neg_eigval: bool = True
    norm_eps: float = 1e-5

    kernels = ("wq", "wk", "wv", "w_fa", "w_fb", "w_beta", "w_ga", "w_gb",
               "wo")

    @property
    def width(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def rank(self) -> int:
        return self.low_rank or self.head_dim

    def weight_specs(self, in_dim: int):
        f, W, r, H = DataType.DT_FLOAT, self.width, self.rank, self.num_heads
        return [
            WeightSpec("wq", (in_dim, W), f, "normal"),
            WeightSpec("wk", (in_dim, W), f, "normal"),
            WeightSpec("wv", (in_dim, W), f, "normal"),
            # taps of the causal depthwise convolution, q | k | v channels
            WeightSpec("conv", (self.conv_kernel, 3 * W), f, "uniform"),
            WeightSpec("w_fa", (in_dim, r), f, "normal"),
            WeightSpec("w_fb", (r, W), f, "normal"),
            WeightSpec("a_log", (H,), f, "zeros"),
            WeightSpec("dt_bias", (W,), f, "zeros"),
            WeightSpec("w_beta", (in_dim, H), f, "normal"),
            WeightSpec("o_norm", (self.head_dim,), f, "ones"),
            WeightSpec("w_ga", (in_dim, r), f, "normal"),
            WeightSpec("w_gb", (r, W), f, "normal"),
            WeightSpec("b_gb", (W,), f, "zeros"),
            WeightSpec("wo", (W, self.embed_dim), f, "normal"),
        ]

    def initializers(self, kernel_initializer=None) -> dict:
        """Taps uniform in +-conv_kernel^-0.5, a decay rate a head
        exp(a_log) in [1, 16] and a time step softplus(dt_bias) in [0.001,
        0.1] (the ranges the published layer draws from); matrices by
        `kernel_initializer` where one is given."""
        from ..initializer import UniformInitializer

        k = self.conv_kernel ** -0.5
        inits = {"conv": UniformInitializer(min_val=-k, max_val=k),
                 "a_log": UniformInitializer(min_val=0.0, max_val=2.7726),
                 "dt_bias": UniformInitializer(min_val=-6.9073,
                                               max_val=-2.2522)}
        if kernel_initializer is not None:
            inits.update(dict.fromkeys(self.kernels, kernel_initializer))
        return inits

    def qkv_in(self, ctx, weights, x):
        """[q~ | k~ | v~] (.., 3 width): the projections before the
        convolution, in x's dtype."""
        with jax.named_scope("kda.proj"):
            return jnp.concatenate(
                [proj(ctx, x, weights[w], None) for w in ("wq", "wk", "wv")],
                axis=-1)

    def conv(self, weights, window, tokens: int):
        """SiLU of the causal depthwise convolution: `window` (rows,
        conv_kernel - 1 + tokens, 3 width) holds each row's earlier
        inputs before its tokens; (rows, tokens, 3 width) float32."""
        with jax.named_scope("kda.conv"):
            return causal_conv(weights["conv"], window, tokens)

    def heads(self, qkv):
        """q (l2-normalised, times d^-0.5), k (l2-normalised), v, each
        (.., heads, d) float32, of the convolved [q | k | v]."""
        H, d = self.num_heads, self.head_dim
        q, k, v = (t.reshape(t.shape[:-1] + (H, d))
                   for t in jnp.split(qkv, 3, axis=-1))

        def l2norm(t):
            return t * jax.lax.rsqrt(
                jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

        return l2norm(q) * d ** -0.5, l2norm(k), v

    def gates(self, ctx, weights, x):
        """(alpha (.., heads, d) in (0, 1), beta (.., heads)) float32."""
        H, d = self.num_heads, self.head_dim
        f = jnp.float32
        with jax.named_scope("kda.gate"):
            g = proj(ctx, proj(ctx, x, weights["w_fa"], None),
                     weights["w_fb"], None).astype(f)
            g = jax.nn.softplus(g + weights["dt_bias"].astype(f))
            rate = jnp.exp(weights["a_log"].astype(f))[:, None]
            alpha = jnp.exp(-rate * g.reshape(g.shape[:-1] + (H, d)))
            beta = jax.nn.sigmoid(
                proj(ctx, x, weights["w_beta"], None).astype(f))
            return alpha, beta * 2.0 if self.neg_eigval else beta

    def output(self, ctx, weights, o, x):
        """o (.., heads, d) float32 -> RMSNorm a head, the low-rank
        sigmoid gate, the output projection; in x's dtype."""
        with jax.named_scope("kda.out"):
            o = rms_norm(o, weights["o_norm"], self.norm_eps)
            gate = proj(ctx, proj(ctx, x, weights["w_ga"], None),
                        weights["w_gb"], weights["b_gb"])
            o = (o.reshape(o.shape[:-2] + (self.width,))
                 * jax.nn.sigmoid(gate.astype(jnp.float32)))
            return proj(ctx, o.astype(x.dtype), weights["wo"], None)

    def linear_flops(self, tokens: int, in_dim: int) -> float:
        W, r = self.width, self.rank
        per_token = (3 * in_dim * W + 2 * (in_dim * r + r * W)
                     + in_dim * self.num_heads + W * self.embed_dim
                     + 3 * W * self.conv_kernel)
        return 2.0 * tokens * per_token

    def state_flops(self, tokens: int) -> float:
        """Decay, S'^T k, the rank-one update and S^T q: four passes."""
        return 2.0 * tokens * 4 * self.num_heads * self.head_dim ** 2


def run_sequences(f: DeltaFrontEnd, ctx, weights, x, live, keep, state,
                  tail, update):
    """The layer over rows of consecutive tokens: x (rows, tokens,
    hidden), live (rows, tokens) with the live tokens leading, keep
    (rows,) false where a row starts from nothing, state (rows, heads, d,
    d), tail (rows, conv_kernel - 1, 3 width). Returns y (rows, tokens,
    hidden), the new state and the new tail: a row's last conv_kernel - 1
    inputs up to its last live token."""
    tokens = x.shape[1]
    window = conv_window(tail, f.qkv_in(ctx, weights, x), keep)
    q, k, v = f.heads(f.conv(weights, window, tokens))
    alpha, beta = f.gates(ctx, weights, x)
    with jax.named_scope("kda.state"):
        o, state = update(state, q, k, v, alpha, beta, live, keep)
    return (f.output(ctx, weights, o, x), state,
            next_tail(window, live, f.conv_kernel))


# ------------------------------------------------------------ training-shaped

@dataclass(frozen=True)
class GatedDeltaAttentionParams:
    front: DeltaFrontEnd

    embed_dim = property(lambda self: self.front.embed_dim)
    num_heads = property(lambda self: self.front.num_heads)


def _delta_weights(p: GatedDeltaAttentionParams, in_shapes):
    return p.front.weight_specs(in_shapes[0][-1])


def _delta_forward(p: GatedDeltaAttentionParams, inputs, weights, state,
                   ctx):
    from ..kernels.delta_rule import delta_rule_reference

    f = p.front
    x = inputs[0]
    b, s, _ = x.shape
    y, _, _ = run_sequences(
        f, ctx, weights, x, jnp.ones((b, s), bool), jnp.zeros((b,), bool),
        jnp.zeros((b, f.num_heads, f.head_dim, f.head_dim), jnp.float32),
        jnp.zeros((b, f.conv_kernel - 1, 3 * f.width), x.dtype),
        delta_rule_reference)
    return [y], state


def _delta_flops(p: GatedDeltaAttentionParams, in_shapes, out_shapes):
    b, s, d = in_shapes[0]
    return p.front.linear_flops(b * s, d) + p.front.state_flops(b * s)


def _delta_decode_layer(layer, ctx):
    # `state_slot`: which slot's state a row reads and writes: row i is slot
    # i, but for a prefill chunk's rows past the slots
    return (OT.OP_GATED_DELTA_ATTENTION_DECODE,
            GatedDeltaDecodeParams(layer.params.front, ctx.slots,
                                   ctx.max_seq, cache_dtype=ctx.at_rest),
            ("positions", "state_slot"))


register_op(OpDef(OT.OP_GATED_DELTA_ATTENTION, infer_shapes, _delta_forward,
                  _delta_weights, _delta_flops,
                  decode_layer=_delta_decode_layer))


# --------------------------------------------------------------------- decode

@dataclass(frozen=True)
class GatedDeltaDecodeParams:
    front: DeltaFrontEnd
    slots: int
    max_seq_len: int
    cache_dtype: DataType = DataType.DT_FLOAT  # of the convolution's tail

    embed_dim = property(lambda self: self.front.embed_dim)
    num_heads = property(lambda self: self.front.num_heads)

    @property
    def state_leaves(self) -> dict:
        """{state leaf: shape} of what the layer keeps a slot."""
        return {w.name: w.shape
                for w in _delta_decode_state(self).weight_specs(self.slots)}


def _delta_decode_state(p: GatedDeltaDecodeParams) -> DecodeState:
    """The delta rule's state and its convolution's last inputs, a slot:
    not paged, not shareable block by block, reset when a slot's row starts
    a request (position 0)."""
    f = p.front
    return slot_state(
        (("state_s", (f.num_heads, f.head_dim, f.head_dim),
          DataType.DT_FLOAT),
         ("state_conv", (f.conv_kernel - 1, 3 * f.width), p.cache_dtype)),
        p.slots, "gated delta-rule attention")


def _delta_decode_forward(p: GatedDeltaDecodeParams, inputs, weights, state,
                          ctx):
    from ..kernels.delta_rule import delta_rule_update

    def run(x, live, keep, S, tail):
        return run_sequences(p.front, ctx, weights, x, live, keep, S, tail,
                             delta_rule_update)

    y, (S, tail) = decode_rows(
        "gated delta attention", p.slots, p.max_seq_len, inputs,
        (weights["state_s"], weights["state_conv"]), run)
    return [y], {"state_s": S, "state_conv": tail}


def _delta_decode_flops(p: GatedDeltaDecodeParams, in_shapes, out_shapes):
    rows, q_len, d = in_shapes[0]
    return (p.front.linear_flops(rows * q_len, d)
            + p.front.state_flops(rows * q_len))


register_op(OpDef(OT.OP_GATED_DELTA_ATTENTION_DECODE, infer_shapes,
                  _delta_decode_forward, _delta_weights,
                  _delta_decode_flops, state=_delta_decode_state,
                  state_leaves=dict(state_s=BY_SLOT, state_conv=BY_SLOT)))
