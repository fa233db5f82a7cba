"""The selective state-space layer (Mamba-1, as Jamba's recurrent layers
carry it: three RMSNorms inside, over the time step's low-rank input and
over B and C), as two ops over one front end. The equations are written
out in models/jamba2_reference.py.

`MambaFrontEnd` owns what both share: the weights and their names, the
input projection to [u | z], the causal depthwise convolution with its
bias and SiLU, the projection of the convolved row to [r | B | C], the
three inner norms, the time step dt = softplus(r W_dt + b_dt), A =
-exp(A_log), the gate SiLU(z) and the output projection. What a sequence
leaves behind is of fixed size: a state h (state_size, inner) float32 (the
published layout is its transpose: here the state's 16 lie on sublanes and
the channels on lanes, which is what a TPU tile wants) and the
convolution's last `conv_kernel - 1` inputs.

- OP_SELECTIVE_SSM, the training-shaped op on (batch, seq, hidden): every
  sequence starts from the zero state, the recurrence is a `lax.scan` over
  its tokens in float32 (kernels/selective_scan.selective_scan_reference),
  plain jnp, differentiable by autodiff.
- OP_SELECTIVE_SSM_DECODE, the decode op: state leaves `state_h` (slots,
  state_size, inner) float32 and `state_conv` (slots, conv_kernel - 1,
  inner) at rest, indexed by SLOT, not by page. Its rows follow the serving
  engine's two layouts of a step exactly as the delta rule's decode op
  does (ops/recurrent.decode_rows): a dead token leaves the state as it
  is, a row whose first live token is at position 0 starts from the zero
  state and an empty convolution window, so a slot given to a new request
  is reset inside the step's program. The slots' rows, one token each, run
  the Pallas kernel (kernels/selective_scan.py) where its gate allows,
  state aliased in place; so does a chunk, whose state stays in VMEM over
  its tokens.
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
class MambaFrontEnd:
    embed_dim: int          # the model's hidden size
    inner: int              # channels: expand x hidden
    state_size: int
    dt_rank: int
    conv_kernel: int = 4
    conv_bias: bool = True
    # of the RMSNorms (a gain each) over dt's low-rank input, B and C
    norm_eps: float = 1e-6

    kernels = ("w_in", "w_x", "w_dt", "w_out")

    def weight_specs(self, in_dim: int):
        f, E, N = DataType.DT_FLOAT, self.inner, self.state_size
        R = self.dt_rank
        specs = [
            WeightSpec("w_in", (in_dim, 2 * E), f, "normal"),
            # taps of the causal depthwise convolution, a channel each
            WeightSpec("conv", (self.conv_kernel, E), f, "uniform"),
            WeightSpec("w_x", (E, R + 2 * N), f, "normal"),
            WeightSpec("w_dt", (R, E), f, "normal"),
            WeightSpec("dt_bias", (E,), f, "zeros"),
            WeightSpec("a_log", (N, E), f, "zeros"),
            WeightSpec("d", (E,), f, "ones"),
            WeightSpec("w_out", (E, self.embed_dim), f, "normal"),
            WeightSpec("dt_norm", (R,), f, "ones"),
            WeightSpec("b_norm", (N,), f, "ones"),
            WeightSpec("c_norm", (N,), f, "ones"),
        ]
        if self.conv_bias:
            specs.insert(2, WeightSpec("conv_bias", (E,), f, "uniform"))
        return specs

    def initializers(self, kernel_initializer=None) -> dict:
        """Taps and their bias uniform in +-conv_kernel^-0.5, A_log =
        log(1 .. state_size) a channel, D ones, a time step's bias so that
        softplus(b_dt) lies in [0.001, 0.1] (the ranges the published
        layer draws from); matrices by `kernel_initializer` where one is
        given."""
        from ..initializer import LogRangeInitializer, UniformInitializer

        k = self.conv_kernel ** -0.5
        taps = UniformInitializer(min_val=-k, max_val=k)
        inits = {"conv": taps, "conv_bias": taps,
                 "a_log": LogRangeInitializer(),
                 "dt_bias": UniformInitializer(min_val=-6.9073,
                                               max_val=-2.2522)}
        if kernel_initializer is not None:
            inits.update(dict.fromkeys(self.kernels, kernel_initializer))
        return inits

    def project(self, ctx, weights, x):
        """(u, z), each (.., inner) in x's dtype: what the convolution
        reads and what gates the output."""
        with jax.named_scope("ssm.proj"):
            uz = proj(ctx, x, weights["w_in"], None)
            return uz[..., :self.inner], uz[..., self.inner:]

    def conv(self, weights, window, tokens: int):
        """c (rows, tokens, inner) float32 of `window` (recurrent.
        conv_window)."""
        with jax.named_scope("ssm.conv"):
            return causal_conv(weights["conv"], window, tokens,
                               weights.get("conv_bias"))

    def parameters(self, ctx, weights, c, dtype):
        """(dt (.., inner), B, C (.., state_size)) float32 of the convolved
        row c: the token's own time step, input and output maps."""
        R, N, f = self.dt_rank, self.state_size, jnp.float32
        with jax.named_scope("ssm.param"):
            rbc = proj(ctx, c.astype(dtype), weights["w_x"], None).astype(f)
            r, B, C = (rms_norm(t, weights[g], self.norm_eps)
                       for t, g in ((rbc[..., :R], "dt_norm"),
                                    (rbc[..., R:R + N], "b_norm"),
                                    (rbc[..., R + N:], "c_norm")))
            dt = proj(ctx, r.astype(dtype), weights["w_dt"], None).astype(f)
            return (jax.nn.softplus(dt + weights["dt_bias"].astype(f)),
                    B, C)

    def output(self, ctx, weights, y, z):
        """(y . SiLU(z)) W_out in z's dtype."""
        with jax.named_scope("ssm.out"):
            zf = z.astype(jnp.float32)
            y = y * (zf * jax.nn.sigmoid(zf))
            return proj(ctx, y.astype(z.dtype), weights["w_out"], None)

    def linear_flops(self, tokens: int, in_dim: int) -> float:
        E, N, R = self.inner, self.state_size, self.dt_rank
        per_token = (in_dim * 2 * E + E * (R + 2 * N) + R * E
                     + E * self.embed_dim + E * self.conv_kernel)
        return 2.0 * tokens * per_token

    def state_flops(self, tokens: int) -> float:
        """The decay's exponent and product, the input's outer product and
        sum, the output's product and sum: some seven passes over h."""
        return 7.0 * tokens * self.inner * self.state_size

    def state_bytes(self) -> int:
        """Bytes of one slot's h: a step reads them and writes them."""
        return 4 * self.inner * self.state_size


def run_sequences(f: MambaFrontEnd, ctx, weights, x, live, keep, h, tail,
                  update):
    """The layer over rows of consecutive tokens: x (rows, tokens,
    hidden), live (rows, tokens) with the live tokens leading, keep
    (rows,) false where a row starts from nothing, h (rows, state_size,
    inner), tail (rows, conv_kernel - 1, inner). Returns y (rows, tokens,
    hidden), the new state and the new tail: a row's last conv_kernel - 1
    inputs up to its last live token."""
    tokens = x.shape[1]
    u, z = f.project(ctx, weights, x)
    window = conv_window(tail, u, keep)
    c = f.conv(weights, window, tokens)
    dt, B, C = f.parameters(ctx, weights, c, x.dtype)
    with jax.named_scope("ssm.state"):
        A = -jnp.exp(weights["a_log"].astype(jnp.float32))
        y, h = update(h, dt, c, B, C, A, weights["d"], live, keep)
    return (f.output(ctx, weights, y, z), h,
            next_tail(window, live, f.conv_kernel))


# ------------------------------------------------------------ training-shaped

@dataclass(frozen=True)
class SelectiveSSMParams:
    front: MambaFrontEnd

    embed_dim = property(lambda self: self.front.embed_dim)


def _ssm_weights(p: SelectiveSSMParams, in_shapes):
    return p.front.weight_specs(in_shapes[0][-1])


def _ssm_forward(p: SelectiveSSMParams, inputs, weights, state, ctx):
    from ..kernels.selective_scan import selective_scan_reference

    f = p.front
    x = inputs[0]
    b, s, _ = x.shape
    y, _, _ = run_sequences(
        f, ctx, weights, x, jnp.ones((b, s), bool), jnp.zeros((b,), bool),
        jnp.zeros((b, f.state_size, f.inner), jnp.float32),
        jnp.zeros((b, f.conv_kernel - 1, f.inner), x.dtype),
        selective_scan_reference)
    return [y], state


def _ssm_flops(p, in_shapes, out_shapes):
    rows, tokens, d = in_shapes[0]
    return (p.front.linear_flops(rows * tokens, d)
            + p.front.state_flops(rows * tokens))


def _ssm_decode_layer(layer, ctx):
    # `state_slot`: which slot's state a row reads and writes: row i is slot
    # i, but for a prefill chunk's rows past the slots
    return (OT.OP_SELECTIVE_SSM_DECODE,
            SelectiveSSMDecodeParams(layer.params.front, ctx.slots,
                                     ctx.max_seq, cache_dtype=ctx.at_rest),
            ("positions", "state_slot"))


register_op(OpDef(OT.OP_SELECTIVE_SSM, infer_shapes, _ssm_forward,
                  _ssm_weights, _ssm_flops, decode_layer=_ssm_decode_layer))


# --------------------------------------------------------------------- decode

@dataclass(frozen=True)
class SelectiveSSMDecodeParams:
    front: MambaFrontEnd
    slots: int
    max_seq_len: int
    cache_dtype: DataType = DataType.DT_FLOAT  # of the convolution's tail

    embed_dim = property(lambda self: self.front.embed_dim)


def _ssm_decode_state(p: SelectiveSSMDecodeParams) -> DecodeState:
    """The layer's h and its convolution's last inputs, a slot; a step's
    span carries the bytes of h its decoding rows read and write."""
    f = p.front
    return slot_state(
        (("state_h", (f.state_size, f.inner), DataType.DT_FLOAT),
         ("state_conv", (f.conv_kernel - 1, f.inner), p.cache_dtype)),
        p.slots, "selective state-space layers",
        step_counts=lambda positions: {
            "ssm_state_bytes": 2 * len(positions) * f.state_bytes()})


def _ssm_decode_forward(p: SelectiveSSMDecodeParams, inputs, weights, state,
                        ctx):
    from ..kernels.selective_scan import selective_scan_update

    def run(x, live, keep, h, tail):
        return run_sequences(p.front, ctx, weights, x, live, keep, h, tail,
                             selective_scan_update)

    y, (h, tail) = decode_rows(
        "selective state-space layer", p.slots, p.max_seq_len, inputs,
        (weights["state_h"], weights["state_conv"]), run)
    return [y], {"state_h": h, "state_conv": tail}


register_op(OpDef(OT.OP_SELECTIVE_SSM_DECODE, infer_shapes,
                  _ssm_decode_forward, _ssm_weights, _ssm_flops,
                  state=_ssm_decode_state,
                  state_leaves=dict(state_h=BY_SLOT, state_conv=BY_SLOT)))
