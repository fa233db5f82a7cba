"""Multi-head attention.

Reference: src/ops/attention.cc (926 LoC) + attention.cu wrapping
`cudnnMultiHeadAttnForward` — a monolithic vendor kernel with weights packed
into a single tensor. TPU-native design instead expresses attention as
projections (MXU GEMMs) + a scaled-dot-product core with three interchangeable
implementations selected per placement:

  - "xla":    plain einsum softmax(QK^T)V — XLA fuses well for short seqs
  - "flash":  Pallas blockwise-softmax kernel (kernels/flash_attention.py) —
    O(seq) memory, used on the real chip for long sequences
  - "ring":   shard_map ring attention over the `seq` mesh axis
    (parallel/ring_attention.py) — the long-context path the reference lacks
    (SURVEY §5: no ring/Ulysses in FlexFlow)

Head-parallelism (the reference's attribute-parallel attention rewrite,
substitution.cc:create_partition_attention_combine) maps to sharding the head
dim of the projection weights over the `model` axis.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..fftype import DataType, OperatorType as OT
from .base import OpDef, WeightSpec, matmul_cast, register_op


@dataclass(frozen=True)
class MultiHeadAttentionParams:
    embed_dim: int
    num_heads: int
    kdim: int = 0  # 0 → embed_dim
    vdim: int = 0
    dropout: float = 0.0
    use_bias: bool = True
    add_bias_kv: bool = False
    add_zero_attn: bool = False
    causal: bool = False  # TPU-native addition (reference cuDNN op is unmasked)
    impl: str = "xla"  # xla | flash | ring
    # rotary positions on q and k (half-rotation form) from a fourth input
    # of positions (batch, seq) int; 0 = none
    rope_theta: float = 0.0
    # RMSNorm over the whole q and k projections (all heads together, as
    # OLMoE does), with learned scales `q_norm` / `k_norm`
    qk_norm: bool = False
    qk_norm_eps: float = 1e-5


def _mha_dims(p: MultiHeadAttentionParams):
    kdim = p.kdim or p.embed_dim
    vdim = p.vdim or p.embed_dim
    return kdim, vdim


def _mha_infer(p: MultiHeadAttentionParams, in_shapes):
    q, k, v = in_shapes[:3]
    return [(q[0], q[1], p.embed_dim)]


def _mha_weights(p: MultiHeadAttentionParams, in_shapes):
    q, k, v = in_shapes[:3]
    kdim, vdim = _mha_dims(p)
    # per-head projection sizes follow attention.cc:70-80 (qProjSize = kdim/heads)
    ws = [
        WeightSpec("wq", (q[-1], p.embed_dim), DataType.DT_FLOAT),
        WeightSpec("wk", (k[-1], p.embed_dim), DataType.DT_FLOAT),
        WeightSpec("wv", (v[-1], p.embed_dim), DataType.DT_FLOAT),
        WeightSpec("wo", (p.embed_dim, p.embed_dim), DataType.DT_FLOAT),
    ]
    if p.use_bias:
        ws += [
            WeightSpec("bq", (p.embed_dim,), DataType.DT_FLOAT, "zeros"),
            WeightSpec("bk", (p.embed_dim,), DataType.DT_FLOAT, "zeros"),
            WeightSpec("bv", (p.embed_dim,), DataType.DT_FLOAT, "zeros"),
            WeightSpec("bo", (p.embed_dim,), DataType.DT_FLOAT, "zeros"),
        ]
    if p.qk_norm:
        ws += [
            WeightSpec("q_norm", (p.embed_dim,), DataType.DT_FLOAT, "ones"),
            WeightSpec("k_norm", (p.embed_dim,), DataType.DT_FLOAT, "ones"),
        ]
    return ws


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
    q_in, k_in, v_in = inputs[:3]
    H = p.num_heads
    E = p.embed_dim
    hd = E // H

    def proj(x, w, b):
        xm, wm = matmul_cast(ctx, x, w.astype(x.dtype))
        y = jnp.dot(xm, wm, preferred_element_type=jnp.float32).astype(x.dtype)
        if b is not None:
            y = y + b.astype(y.dtype)
        return y

    q = proj(q_in, weights["wq"], weights.get("bq"))
    k = proj(k_in, weights["wk"], weights.get("bk"))
    v = proj(v_in, weights["wv"], weights.get("bv"))
    scale = 1.0 / math.sqrt(hd)
    # between the projections and the kernel, on the packed layout
    if p.qk_norm:
        from .core import rms_norm

        q = rms_norm(q, weights["q_norm"], p.qk_norm_eps)
        k = rms_norm(k, weights["k_norm"], p.qk_norm_eps)
    if p.rope_theta:
        cos, sin = rope_cos_sin(inputs[3], hd, p.rope_theta)
        q = apply_rope(q, cos, sin, H)
        k = apply_rope(k, cos, sin, H)

    if p.impl == "flash":
        # the plan's shards of the kernel's operands: batch as the output
        # places it, heads as wq's columns are placed (head-parallel);
        # each shard runs the kernel on its own batch rows and heads
        from ..kernels.dispatch import per_shard, shards_of, spec_entries

        batch_ax = spec_entries(ctx.out_spec, 1)[0]
        head_ax = spec_entries((ctx.weight_axes or {}).get("wq"), 2)[1]
        if q.shape[0] % shards_of(ctx.mesh, batch_ax):
            batch_ax = None
        if H % shards_of(ctx.mesh, head_ax):
            head_ax = None
        h_local = H // shards_of(ctx.mesh, head_ax)

    if p.impl == "flash" and getattr(ctx, "flash_packed", True):
        # packed layout: the kernel selects heads with lane-offset block
        # index maps, so the projections' (b, s, H·hd) output feeds it
        # directly — no (b,s,h,d)→(b,h,s,d) HBM relayout in fwd OR bwd.
        # ctx.flash_packed=False (--flash-transposed) forces the
        # head-transposed kernels below — the relayout ablation baseline.
        from ..kernels.flash_attention import flash_attention_packed

        spec = PartitionSpec(batch_ax, None, head_ax)
        out = per_shard(
            functools.partial(flash_attention_packed, num_heads=h_local,
                              causal=p.causal, scale=scale),
            ctx.mesh, (spec, spec, spec), spec)(q, k, v)
        y = proj(out, weights["wo"], weights.get("bo"))
        return [y], state

    def split_heads(x):
        b, s, _ = x.shape
        return x.reshape(b, s, H, hd).transpose(0, 2, 1, 3)

    q, k, v = split_heads(q), split_heads(k), split_heads(v)

    if p.impl == "ring":
        from ..parallel.ring_attention import ring_attention

        out = ring_attention(q, k, v, causal=p.causal, scale=scale,
                             mesh=ctx.mesh,
                             overlap=getattr(ctx, "overlap_collectives", True))
    elif p.impl == "flash":
        # transposed-layout flash (flash_packed=False): same kernel math,
        # but the head split/merge above materializes the
        # (b,s,h,d)↔(b,h,s,d) relayouts the packed path avoids
        from ..kernels.flash_attention import flash_attention

        spec = PartitionSpec(batch_ax, head_ax)
        out = per_shard(
            functools.partial(flash_attention, causal=p.causal, scale=scale),
            ctx.mesh, (spec, spec, spec), spec)(q, k, v)
    else:
        out = sdpa_xla(q, k, v, causal=p.causal, scale=scale)

    b, _, s, _ = out.shape
    out = out.transpose(0, 2, 1, 3).reshape(b, s, E)
    y = proj(out, weights["wo"], weights.get("bo"))
    return [y], state


def _mha_flops(p: MultiHeadAttentionParams, in_shapes, out_shapes):
    q, k, v = in_shapes[:3]
    b, sq, dq = q
    sk = k[1]
    E = p.embed_dim
    proj = 2.0 * b * (sq * dq * E + sk * k[2] * E + sk * v[2] * E + sq * E * E)
    attn = 2.0 * b * p.num_heads * sq * sk * (E // p.num_heads) * 2
    return proj + attn


register_op(
    OpDef(OT.OP_MULTIHEAD_ATTENTION, _mha_infer, _mha_forward, _mha_weights, _mha_flops)
)
