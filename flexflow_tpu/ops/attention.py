"""Multi-head attention, and the one definition of an attention layer's
front end.

Reference: src/ops/attention.cc (926 LoC) + attention.cu wrapping
`cudnnMultiHeadAttnForward` — a monolithic vendor kernel with weights packed
into a single tensor. TPU-native design instead expresses attention as
projections (MXU GEMMs) + a scaled-dot-product core.

`AttentionFrontEnd` owns what every attention op shares: the trainable
weights and their names, the input projections with QK-norm and RoPE, the
output projection, their FLOP count and the head-parallel sharding rule.
The op here and the two decode ops (ops/inc_attention.py) hold one as
`params.front`; search/, parallel/ and the decode replay ask it and name
no weight themselves. What is this op's alone is the core, selected per
placement:

  - "xla":    plain einsum softmax(QK^T)V — XLA fuses well for short seqs
  - "flash":  the packed Pallas kernels (kernels/flash_attention.py), run
    per shard of the plan — O(seq) memory, no head-transpose relayout
  - "ring":   shard_map ring attention over the `seq` mesh axis
    (parallel/ring_attention.py) — the long-context path the reference lacks
    (SURVEY §5: no ring/Ulysses in FlexFlow)
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import ClassVar

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..fftype import DataType, OperatorType as OT
from .base import OpDef, WeightSpec, matmul_cast, register_op


def proj(ctx, x, w, b):
    """x @ w (+ b): operands in the MXU input dtype, float32 accumulation,
    the result in x's dtype."""
    xm, wm = matmul_cast(ctx, x, w.astype(x.dtype))
    y = jnp.dot(xm, wm, preferred_element_type=jnp.float32).astype(x.dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


@dataclass(frozen=True)
class AttentionFrontEnd:
    """What an attention layer is before and after its core, whichever op
    runs the core: the decode replay hands a trained layer's front end to
    the decode op as this one value, and weights move by these names."""

    embed_dim: int
    num_heads: int
    use_bias: bool = True
    # rotary positions on q and k (half-rotation form) from positions
    # (batch, seq) int; 0 = none
    rope_theta: float = 0.0
    # RMSNorm over the whole q and k projections (all heads together, as
    # OLMoE does), with learned scales `q_norm` / `k_norm`
    qk_norm: bool = False
    qk_norm_eps: float = 1e-5
    # grouped keys and values: `num_kv_heads` heads of k and v, query head
    # i reading KV head i // (num_heads // num_kv_heads); 0 = num_heads
    num_kv_heads: int = 0
    # a head's size where it is not embed_dim / num_heads (q, the gate
    # and the core are then num_heads * head_size wide, `wo` brings them
    # back to embed_dim); 0 = embed_dim // num_heads
    head_size: int = 0
    # the core's output times sigmoid(x @ wg), elementwise, before `wo`
    output_gate: bool = False

    # the four every attention layer has; `wg` joins them under
    # `output_gate` (`matrices`)
    kernels: ClassVar[tuple] = ("wq", "wk", "wv", "wo")

    @property
    def matrices(self) -> tuple:
        """The weights a kernel initializer draws."""
        return self.kernels + (("wg",) if self.output_gate else ())

    @property
    def head_dim(self) -> int:
        return self.head_size or self.embed_dim // self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def q_width(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_width(self) -> int:
        """Numbers a token's key (and its value) holds: a cache row."""
        return self.kv_heads * self.head_dim

    def weight_specs(self, q_dim: int, k_dim: int, v_dim: int):
        """The trainable weights, in the order parameters are initialised.
        Per-head projection sizes follow attention.cc:70-80."""
        E, Q, KV = self.embed_dim, self.q_width, self.kv_width
        f = DataType.DT_FLOAT
        ws = [WeightSpec("wq", (q_dim, Q), f), WeightSpec("wk", (k_dim, KV), f),
              WeightSpec("wv", (v_dim, KV), f), WeightSpec("wo", (Q, E), f)]
        if self.output_gate:
            ws.append(WeightSpec("wg", (q_dim, Q), f))
        if self.use_bias:
            ws += [WeightSpec(b, (n,), f, "zeros")
                   for b, n in (("bq", Q), ("bk", KV), ("bv", KV), ("bo", E))]
        if self.qk_norm:
            ws += [WeightSpec(g, (n,), f, "ones")
                   for g, n in (("q_norm", Q), ("k_norm", KV))]
        return ws

    def qkv(self, ctx, weights, q_in, k_in, v_in, positions=None):
        """The projections, then QK-norm, then RoPE, all on the packed
        (batch, seq, heads * head_dim) layout."""
        q = proj(ctx, q_in, weights["wq"], weights.get("bq"))
        k = proj(ctx, k_in, weights["wk"], weights.get("bk"))
        v = proj(ctx, v_in, weights["wv"], weights.get("bv"))
        if self.qk_norm:
            from .core import rms_norm

            q = rms_norm(q, weights["q_norm"], self.qk_norm_eps)
            k = rms_norm(k, weights["k_norm"], self.qk_norm_eps)
        if self.rope_theta:
            cos, sin = rope_cos_sin(positions, self.head_dim,
                                    self.rope_theta)
            q = apply_rope(q, cos, sin, self.num_heads)
            k = apply_rope(k, cos, sin, self.kv_heads)
        return q, k, v

    def output(self, ctx, weights, o, x=None):
        """The output projection of the core's `o`; `x`, the layer's
        input, feeds the output gate where the front end has one."""
        if self.output_gate:
            gate = proj(ctx, x, weights["wg"], None)
            o = o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
        return proj(ctx, o, weights["wo"], weights.get("bo"))

    def linear_flops(self, batch, q_rows, kv_rows, q_dim, k_dim, v_dim):
        """FLOPs of the projections over a batch of q_rows queries and
        kv_rows keys and values."""
        E, Q, KV = self.embed_dim, self.q_width, self.kv_width
        gate = q_rows * q_dim * Q if self.output_gate else 0
        return 2.0 * batch * (q_rows * q_dim * Q + kv_rows * k_dim * KV
                              + kv_rows * v_dim * KV + q_rows * Q * E + gate)

    def head_parallel_ok(self, degree: int) -> bool:
        return (self.num_heads % degree == 0 and self.kv_heads % degree == 0
                and self.embed_dim % degree == 0)

    def head_parallel(self, axis):
        """(weight name, PartitionSpec) of heads split over mesh axis
        `axis`: Q, K, V (and the gate) column-parallel with their biases,
        O row-parallel (its partial sums are the caller's psum) with its
        bias whole."""
        cols = ("wq", "wk", "wv") + (("wg",) if self.output_gate else ())
        return (*((w, PartitionSpec(None, axis)) for w in cols),
                *((b, PartitionSpec(axis)) for b in ("bq", "bk", "bv")),
                ("wo", PartitionSpec(axis, None)), ("bo", PartitionSpec()))


class FrontEndFields:
    """An attention op's Params hold the front end as `front`; the fields
    the search, the engine and the analysis passes read stay reachable
    under their own names."""

    embed_dim = property(lambda self: self.front.embed_dim)
    num_heads = property(lambda self: self.front.num_heads)
    use_bias = property(lambda self: self.front.use_bias)


@dataclass(frozen=True)
class MultiHeadAttentionParams(FrontEndFields):
    front: AttentionFrontEnd
    kdim: int = 0  # 0 → embed_dim
    vdim: int = 0
    dropout: float = 0.0
    add_bias_kv: bool = False
    add_zero_attn: bool = False
    causal: bool = False  # TPU-native addition (reference cuDNN op is unmasked)
    impl: str = "xla"  # xla | flash | ring


def _mha_infer(p: MultiHeadAttentionParams, in_shapes):
    q, k, v = in_shapes[:3]
    return [(q[0], q[1], p.embed_dim)]


def _mha_weights(p: MultiHeadAttentionParams, in_shapes):
    q, k, v = in_shapes[:3]
    return p.front.weight_specs(q[-1], k[-1], v[-1])


def rope_cos_sin(positions, head_dim: int, theta: float):
    """cos and sin (batch, seq, head_dim) float32 of the half-rotation
    form: frequencies theta^(-2i/head_dim), i < head_dim/2, each used for
    lanes i and i + head_dim/2."""
    inv_freq = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                         / head_dim)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, cos, sin, num_heads: int):
    """x * cos + rotate_half(x) * sin on the packed layout (batch, seq,
    heads * head_dim): per head, rotate_half(x) = concat(-x2, x1) of its
    two halves. float32 arithmetic, one cast back."""
    b, s, e = x.shape
    xf = x.astype(jnp.float32).reshape(b, s, num_heads, e // num_heads)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    y = xf * cos[:, :, None, :] + rot * sin[:, :, None, :]
    return y.reshape(b, s, e).astype(x.dtype)


def sdpa_xla(q, k, v, *, causal: bool, scale: float):
    """Reference-semantics scaled dot-product attention, einsum form.
    q,k,v: (batch, heads, seq, head_dim)."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def _mha_forward(p: MultiHeadAttentionParams, inputs, weights, state, ctx):
    front = p.front
    H = front.num_heads
    q, k, v = front.qkv(ctx, weights, *inputs)  # positions fourth, with RoPE
    scale = 1.0 / math.sqrt(front.head_dim)
    group = H // front.kv_heads
    impl = p.impl
    if group > 1 and impl != "xla":
        # the packed and ring kernels select q, k and v heads by one lane
        # offset: one head count. Grouped keys and values are repeated
        # and take the einsum (no training cell runs such a layer)
        from ..kernels.dispatch import warn_reference

        warn_reference("multihead_attention", tuple(q.shape),
                       f"{front.kv_heads} KV heads under {H} query heads: "
                       f"the {impl} kernels keep one head count")
        impl = "xla"

    if impl == "flash":
        # the packed kernels select heads with lane-offset block index
        # maps, so the projections' (b, s, H·hd) output feeds them
        # directly: no (b,s,h,d)→(b,h,s,d) HBM relayout in fwd or bwd.
        # Each shard of the plan runs the kernel on its own batch rows and
        # heads: batch as the output places it, heads as wq's columns are
        # placed (head-parallel)
        from ..kernels.dispatch import per_shard, shards_of, spec_entries
        from ..kernels.flash_attention import flash_attention_packed

        batch_ax = spec_entries(ctx.out_spec, 1)[0]
        head_ax = spec_entries((ctx.weight_axes or {}).get("wq"), 2)[1]
        if q.shape[0] % shards_of(ctx.mesh, batch_ax):
            batch_ax = None
        if H % shards_of(ctx.mesh, head_ax):
            head_ax = None
        spec = PartitionSpec(batch_ax, None, head_ax)
        out = per_shard(
            functools.partial(flash_attention_packed,
                              num_heads=H // shards_of(ctx.mesh, head_ax),
                              causal=p.causal, scale=scale),
            ctx.mesh, (spec, spec, spec), spec)(q, k, v)
        return [front.output(ctx, weights, out, inputs[0])], state

    def split_heads(x):
        b, s, _ = x.shape
        x = x.reshape(b, s, -1, front.head_dim).transpose(0, 2, 1, 3)
        # query head i reads KV head i // group
        return x if x.shape[1] == H else jnp.repeat(x, group, axis=1)

    q, k, v = split_heads(q), split_heads(k), split_heads(v)
    if impl == "ring":
        from ..parallel.ring_attention import ring_attention

        out = ring_attention(q, k, v, causal=p.causal, scale=scale,
                             mesh=ctx.mesh,
                             overlap=getattr(ctx, "overlap_collectives", True))
    else:
        with jax.named_scope("gqa.attend"):
            out = sdpa_xla(q, k, v, causal=p.causal, scale=scale)
    b, _, s, _ = out.shape
    out = out.transpose(0, 2, 1, 3).reshape(b, s, front.q_width)
    return [front.output(ctx, weights, out, inputs[0])], state


def _mha_flops(p: MultiHeadAttentionParams, in_shapes, out_shapes):
    q, k, v = in_shapes[:3]
    b, sq, sk = q[0], q[1], k[1]
    attn = 2.0 * b * p.num_heads * sq * sk * p.front.head_dim * 2
    return p.front.linear_flops(b, sq, sk, q[2], k[2], v[2]) + attn


register_op(
    OpDef(OT.OP_MULTIHEAD_ATTENTION, _mha_infer, _mha_forward, _mha_weights, _mha_flops)
)
