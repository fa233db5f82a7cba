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
    per shard of the plan — O(seq) memory, no head-transpose relayout; a
    layer with grouped keys and values repeats them to the query heads in
    front of the kernels
  - "ring":   shard_map ring attention over the `seq` mesh axis
    (parallel/ring_attention.py) — the long-context path the reference lacks
    (SURVEY §5: no ring/Ulysses in FlexFlow)
"""

from __future__ import annotations

import contextlib
import functools
import math
from dataclasses import dataclass
from typing import ClassVar, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..fftype import DataType, OperatorType as OT
from .base import (
    HANDOFF, QUERIES, REWIND, OpDef, WeightSpec, matmul_cast, register_op,
)


def proj(ctx, x, w, b):
    """x @ w (+ b): operands in the MXU input dtype, float32 accumulation,
    the result in x's dtype."""
    xm, wm = matmul_cast(ctx, x, w.astype(x.dtype))
    y = jnp.dot(xm, wm, preferred_element_type=jnp.float32).astype(x.dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


@dataclass(frozen=True)
class Indexer:
    """DeepSeek-Sparse-Attention's lightning indexer on a softmax layer
    with keys and values of its own (Keye-VL-2.0's `sa_config`;
    models/keye_vl2_reference.py writes the equations out): `n_heads`
    queries of `head_dim` and their weights projected from the hidden
    state, one key of `head_dim` a token (LayerNorm with a bias), the
    first `rope_dim` lanes of each rotated at the layer's `rope_theta`
    (half-rotation form); a query row attends the `topk` earlier tokens of
    largest sum_j w_j relu(q_j . k). Latent attention carries its own
    (ops/latent_attention.LatentFrontEnd, whose indexer queries come from
    the query latent)."""

    n_heads: int
    head_dim: int
    topk: int
    rope_dim: int
    norm_eps: float = 1e-6


# what a cache with an indexer's keys beside its rows cannot follow
# (DecodeState.cannot)
SELECTION_CANNOT = dict.fromkeys(
    (HANDOFF, QUERIES),
    "a learned sparse selection (an indexer pool beside the cache rows: "
    "{layer}, ...): the indexer's keys are neither handed off nor scored by "
    "a multi-token call")


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
    # RMSNorm of q and k before RoPE, with learned scales `q_norm` /
    # `k_norm`: "projection" (or True) over the whole projections, all
    # heads together, as OLMoE does; "head" over each head on its own,
    # one scale of head_dim for all of them (Keye-VL-2.0's family);
    # False = none
    qk_norm: object = False
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
    # a learned selection of the positions a row attends (`Indexer`);
    # None = all of its past
    index: Optional[Indexer] = None
    # a value head's size where it is not the key head's: `wv`, the value
    # pool's row and `wo`'s input follow it; 0 = head_dim
    v_head_size: int = 0
    # lanes of each q and k head that RoPE rotates, the first of the head
    # (half-rotation form inside them, frequencies over `rope_dim`); 0 =
    # the whole head
    rope_dim: int = 0
    # keys a row attends, the nearest ones and its own among them (a
    # sliding window); 0 = all of its past. A layer with a window is in
    # the serving cache's window group (serving/paged.py)
    window: int = 0
    # a learned bias a head, `sink` (num_heads,), that joins the softmax's
    # denominator and gives no value
    sink: bool = False
    # the values times this (equal to scaling the core's output)
    value_scale: float = 1.0
    # RoPE turns lanes 2j and 2j + 1 of a head as a pair (GPT-J's form,
    # `position_embedding_type: rope_gptj`) where the default pairs lanes j
    # and j + d / 2; the frequencies are the same, theta^(-2j/d)
    rope_interleaved: bool = False
    # EVA attention (Zheng et al., arXiv:2302.04542, as EvaByte runs it;
    # models/evabyte_reference.py writes the equations out): > 0 makes
    # `window` ALIGNED (a row at t attends the exact keys from
    # window * (t // window) on) and has it attend, under the same softmax,
    # one learned summary for every `summary_chunk` keys of the windows
    # already closed: softmax_m(k_m . phi)-weighted sums of a chunk's
    # rotated keys (+ `mu_k`) and of its values, `phi` and `mu_k` a vector
    # a head. A serving layer keeps the exact rows in the window group and
    # the summaries, a row a chunk, in the global one (serving/paged.py)
    summary_chunk: int = 0

    # the four every attention layer has; `wg` joins them under
    # `output_gate`, the indexer's three under `index` (`matrices`)
    kernels: ClassVar[tuple] = ("wq", "wk", "wv", "wo")

    def __post_init__(self):
        if self.qk_norm not in (False, True, "projection", "head"):
            raise ValueError(
                f"AttentionFrontEnd.qk_norm is False, 'projection' (True) "
                f"or 'head', got {self.qk_norm!r}")
        if self.index is not None and not self.rope_theta:
            raise ValueError(
                "AttentionFrontEnd.index rotates its queries and key: it "
                "needs rope_theta and positions")
        if self.index is not None and (self.window or self.sink
                                       or self.v_head_size):
            raise ValueError(
                "AttentionFrontEnd.index selects over a layer's whole past "
                "with keys and values of one size: no window, sink or "
                "v_head_size beside it")
        if self.summary_chunk and (
                not self.window or self.window % self.summary_chunk
                or self.sink or self.index is not None
                or self.v_head_size or self.kv_heads != self.num_heads):
            raise ValueError(
                f"AttentionFrontEnd.summary_chunk summarises the chunks of "
                f"an aligned `window` it divides, over as many KV heads as "
                f"query heads of one size, with no sink and no indexer; got "
                f"chunk {self.summary_chunk}, window {self.window}, "
                f"{self.kv_heads} KV heads under {self.num_heads}")
        if self.rope_dim % 2 or self.rope_dim > self.head_dim:
            raise ValueError(
                f"AttentionFrontEnd.rope_dim is an even number of a head's "
                f"{self.head_dim} lanes, got {self.rope_dim}")

    @property
    def matrices(self) -> tuple:
        """The weights a kernel initializer draws."""
        return (self.kernels + (("wg",) if self.output_gate else ())
                + (("wi_q", "wi_k", "wi_w") if self.index else ()))

    @property
    def qk_norm_width(self) -> tuple:
        """(numbers `q_norm` scales, numbers `k_norm` scales); (0, 0)
        without QK-norm."""
        if not self.qk_norm:
            return 0, 0
        if self.qk_norm == "head":
            return self.head_dim, self.head_dim
        return self.q_width, self.kv_width

    @property
    def kind(self) -> str:
        """The prefix of the layer's trace scopes (docs/observability.md):
        `gsa` under a learned selection, `eva` under an aligned window
        beside chunk summaries, `swa` under a sliding window, `gqa`
        otherwise."""
        return ("gsa" if self.index else "eva" if self.summary_chunk
                else "swa" if self.window else "gqa")

    def scope(self, part: str):
        """The trace scope of a part of the layer, `qkv` or `out`, under
        its kind: `gsa.qkv`, `swa.out`, ..."""
        return jax.named_scope(f"{self.kind}.{part}")

    @property
    def attend_scope(self) -> str:
        """The trace scope of the layer's core: `gsa.attend`,
        `swa.attend` or `gqa.attend`."""
        return f"{self.kind}.attend"

    @property
    def head_dim(self) -> int:
        return self.head_size or self.embed_dim // self.num_heads

    @property
    def v_head_dim(self) -> int:
        return self.v_head_size or self.head_dim

    @property
    def plain_core(self) -> bool:
        """The core is softmax(q . k) v over a row's whole past with keys
        and values of one size: what the packed training kernels, ring
        attention, the contiguous decode kernel and the paged chunk kernel
        compute."""
        return not (self.window or self.sink
                    or self.v_head_dim != self.head_dim)

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def q_width(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_width(self) -> int:
        """Numbers a token's key holds: a row of the key cache."""
        return self.kv_heads * self.head_dim

    @property
    def v_width(self) -> int:
        """Numbers a token's value holds: a row of the value cache."""
        return self.kv_heads * self.v_head_dim

    @property
    def o_width(self) -> int:
        """Numbers the core gives a row: `wo`'s input."""
        return self.num_heads * self.v_head_dim

    def selected(self, cached_rows: int) -> int:
        """Positions a row attends at the most over a cache of
        `cached_rows` rows a slot under the learned selection; 0 where
        the layer has none, or the cache holds no more than `topk`: the
        selection is then everything, and the layer is served as a plain
        one (the indexer's weights lie unused)."""
        if self.index and cached_rows > self.index.topk:
            return self.index.topk
        return 0

    @property
    def cannot_follow(self) -> dict:
        """What the layer's cache cannot follow (DecodeState.cannot): a
        prompt's whole extent is not in a window layer's pool to hand off,
        nor a block freed behind an advanced cursor to rewind to."""
        if self.index is not None:
            return SELECTION_CANNOT
        if self.summary_chunk:
            return dict.fromkeys(
                (HANDOFF, REWIND, QUERIES),
                "layers that attend an aligned window beside chunk "
                "summaries ({layer}, ...): their exact rows are kept for the "
                "current window only and a summary is written once its chunk "
                "is whole, which is neither handed off, rolled back nor "
                "scored by a multi-token call")
        if self.window:
            return dict.fromkeys(
                (HANDOFF, REWIND),
                "window attention layers ({layer}, ...): their cache group "
                "keeps a slot's window only, which is neither handed off "
                "whole nor rolled back")
        return {}

    def cache_row_widths(self, cached_rows: int) -> dict:
        """{pool leaf: numbers a token holds in it} of the layer's paged
        decode op over a cache of `cached_rows` rows a slot: keys and
        values in a pool each, or, under a learned selection, side by
        side in one row (a selected token is one gathered row:
        kernels/sparse_grouped_attention.py) beside the indexer's key,
        which is stored in a row of the next multiple of 128 (zeros
        behind it): a TPU lays out an array whose last dimension is no
        multiple of its 128 lanes with another dimension innermost, and
        every step then copies the whole pool into the row layout and
        back (ops/latent_attention.LatentFrontEnd.cache_row_widths)."""
        if self.selected(cached_rows):
            return {"pool_kv": 2 * self.kv_width,
                    "pool_i": -(-self.index.head_dim // 128) * 128}
        return {"pool_k": self.kv_width, "pool_v": self.v_width}

    def weight_specs(self, q_dim: int, k_dim: int, v_dim: int):
        """The trainable weights, in the order parameters are initialised.
        Per-head projection sizes follow attention.cc:70-80."""
        E, Q, KV = self.embed_dim, self.q_width, self.kv_width
        V, O = self.v_width, self.o_width
        f = DataType.DT_FLOAT
        ws = [WeightSpec("wq", (q_dim, Q), f), WeightSpec("wk", (k_dim, KV), f),
              WeightSpec("wv", (v_dim, V), f), WeightSpec("wo", (O, E), f)]
        if self.output_gate:
            ws.append(WeightSpec("wg", (q_dim, O), f))
        if self.use_bias:
            ws += [WeightSpec(b, (n,), f, "zeros")
                   for b, n in (("bq", Q), ("bk", KV), ("bv", V), ("bo", E))]
        if self.sink:
            ws.append(WeightSpec("sink", (self.num_heads,), f, "zeros"))
        if self.summary_chunk:
            ws += [WeightSpec(name, (self.kv_heads, self.head_dim), f,
                              "uniform") for name in ("phi", "mu_k")]
        if self.qk_norm:
            ws += [WeightSpec(g, (n,), f, "ones")
                   for g, n in zip(("q_norm", "k_norm"),
                                   self.qk_norm_width)]
        if self.index:
            nI, dI = self.index.n_heads, self.index.head_dim
            ws += [WeightSpec("wi_q", (q_dim, nI * dI), f),
                   WeightSpec("wi_k", (q_dim, dI), f),
                   WeightSpec("wi_k_norm", (dI,), f, "ones"),
                   WeightSpec("wi_k_bias", (dI,), f, "zeros"),
                   WeightSpec("wi_w", (q_dim, nI), f)]
        return ws

    def qkv(self, ctx, weights, q_in, k_in, v_in, positions=None):
        """The projections, then QK-norm, then RoPE, all on the packed
        (batch, seq, heads * head_dim) layout."""
        with self.scope("qkv"):
            q = proj(ctx, q_in, weights["wq"], weights.get("bq"))
            k = proj(ctx, k_in, weights["wk"], weights.get("bk"))
            v = proj(ctx, v_in, weights["wv"], weights.get("bv"))
            if self.qk_norm:
                from .core import rms_norm

                def norm(x, scale):
                    if self.qk_norm != "head":
                        return rms_norm(x, scale, self.qk_norm_eps)
                    heads = x.reshape(x.shape[:-1] + (-1, self.head_dim))
                    return rms_norm(heads, scale,
                                    self.qk_norm_eps).reshape(x.shape)

                q, k = norm(q, weights["q_norm"]), norm(k, weights["k_norm"])
            if self.rope_theta and (self.rope_dim or self.rope_interleaved):
                q = self._rope_leading(q, positions, self.num_heads)
                k = self._rope_leading(k, positions, self.kv_heads)
            elif self.rope_theta:
                cos, sin = rope_cos_sin(positions, self.head_dim,
                                        self.rope_theta)
                q = apply_rope(q, cos, sin, self.num_heads)
                k = apply_rope(k, cos, sin, self.kv_heads)
            if self.value_scale != 1.0:
                v = (v.astype(jnp.float32)
                     * self.value_scale).astype(v.dtype)
        return q, k, v

    def _rope_leading(self, x, positions, heads: int):
        """The first `rope_dim` lanes of every head of x (batch, seq,
        heads * head_dim) rotated (the whole head at 0), in the form
        `rope_interleaved` says, the others as they are."""
        dr = self.rope_dim or self.head_dim
        inv_freq = self.rope_theta ** (
            -jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
        angles = positions.astype(jnp.float32)[..., None, None] * inv_freq
        xh = x.reshape(x.shape[:-1] + (heads, self.head_dim))
        turn = rope_pairs if self.rope_interleaved else rope_half
        return jnp.concatenate(
            [turn(xh[..., :dr], angles), xh[..., dr:]],
            axis=-1).reshape(x.shape)

    def summaries(self, weights, k, v):
        """The summaries of whole chunks: k, v (.., summary_chunk, heads *
        head_dim), a chunk's rotated keys and its values, give (ksum, vsum)
        (.., heads * head_dim) in their dtype. The sums are taken in
        float32 and rounded once."""
        lead, heads = k.shape[:-1], (self.kv_heads, self.head_dim)
        kf = k.astype(jnp.float32).reshape(lead + heads)
        vf = v.astype(jnp.float32).reshape(lead + heads)
        phi = weights["phi"].astype(jnp.float32)
        a = jax.nn.softmax(jnp.einsum("...mhd,hd->...mh", kf, phi), axis=-2)
        ksum = (jnp.einsum("...mh,...mhd->...hd", a, kf)
                + weights["mu_k"].astype(jnp.float32))
        vsum = jnp.einsum("...mh,...mhd->...hd", a, vf)
        wide = lead[:-1] + (-1,)
        return (ksum.reshape(wide).astype(k.dtype),
                vsum.reshape(wide).astype(v.dtype))

    def rows_attended(self, position: int) -> tuple:
        """(exact rows, summary rows) a row at `position` attends under
        `summary_chunk`."""
        return (position % self.window + 1,
                position // self.window * (self.window // self.summary_chunk))

    def step_counts(self, positions) -> dict:
        """What one such layer reads and writes for a step's decoding rows
        at `positions` (DecodeState.step_counts): the exact and summary
        rows they attend, the summaries their chunks complete, the rows
        that open a window (the slot gives the closed one's blocks back)."""
        k = self.kind
        rows = [self.rows_attended(t) for t in positions]
        return {
            f"{k}_exact_rows": sum(r[0] for r in rows),
            f"{k}_summary_rows": sum(r[1] for r in rows),
            f"{k}_summaries_written": sum(
                t % self.summary_chunk == self.summary_chunk - 1
                for t in positions),
            f"{k}_rollovers": sum(t > 0 and t % self.window == 0
                                  for t in positions)}

    def index_inputs(self, ctx, weights, x, positions):
        """What the learned selection of x (batch, seq, hidden) at
        `positions` (batch, seq) starts from: the indexer's queries qi
        (batch, seq, n_heads, head_dim) and key ki (batch, seq, head_dim),
        rotated, and the heads' weights wt (batch, seq, n_heads)
        float32."""
        ix = self.index
        nI, dI, dr = ix.n_heads, ix.head_dim, ix.rope_dim
        inv_freq = self.rope_theta ** (
            -jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
        angles = positions.astype(jnp.float32)[..., None] * inv_freq
        with jax.named_scope("dsa.index"):
            qi = proj(ctx, x, weights["wi_q"], None).reshape(
                x.shape[:-1] + (nI, dI))
            qi = jnp.concatenate(
                [rope_half(qi[..., :dr], angles[..., None, :]),
                 qi[..., dr:]], axis=-1)
            ki = layer_norm(proj(ctx, x, weights["wi_k"], None),
                            weights["wi_k_norm"], weights["wi_k_bias"],
                            ix.norm_eps)
            ki = jnp.concatenate([rope_half(ki[..., :dr], angles),
                                  ki[..., dr:]], axis=-1)
            wt = (proj(ctx, x, weights["wi_w"], None).astype(jnp.float32)
                  * (nI ** -0.5) * (dI ** -0.5))
        return qi, ki, wt

    def output(self, ctx, weights, o, x=None):
        """The output projection of the core's `o`; `x`, the layer's
        input, feeds the output gate where the front end has one."""
        with self.scope("out"):
            if self.output_gate:
                gate = proj(ctx, x, weights["wg"], None)
                o = o * jax.nn.sigmoid(
                    gate.astype(jnp.float32)).astype(o.dtype)
            return proj(ctx, o, weights["wo"], weights.get("bo"))

    def linear_flops(self, batch, q_rows, kv_rows, q_dim, k_dim, v_dim):
        """FLOPs of the projections over a batch of q_rows queries and
        kv_rows keys and values."""
        E, Q, KV = self.embed_dim, self.q_width, self.kv_width
        V, O = self.v_width, self.o_width
        gate = q_rows * q_dim * O if self.output_gate else 0
        if self.index:
            gate += q_rows * q_dim * (
                (self.index.n_heads + 1) * self.index.head_dim
                + self.index.n_heads)
        return 2.0 * batch * (q_rows * q_dim * Q + kv_rows * k_dim * KV
                              + kv_rows * v_dim * V + q_rows * O * E + gate)

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


def rope_half(x, angles):
    """Pairs (x[i], x[i + d/2]) rotated by angles (.., d / 2); float32
    arithmetic, one cast back."""
    xf = x.astype(jnp.float32)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = jnp.split(xf, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def rope_pairs(x, angles):
    """Pairs (x[2i], x[2i + 1]) rotated by angles (.., d / 2); float32
    arithmetic, one cast back."""
    xf = x.astype(jnp.float32)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = xf[..., 0::2], xf[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def layer_norm(x, scale, bias, eps):
    """LayerNorm over the last dimension with a scale and a bias, float32
    arithmetic (the indexers' key norm)."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def softmax_with_sink(logits, sink=None):
    """softmax over the last dimension; `sink`, where given and
    broadcastable to logits[..., :1], is one more logit in the
    denominator that gives no value. float32."""
    if sink is None:
        return jax.nn.softmax(logits, axis=-1)
    sink = sink.astype(jnp.float32)
    m = jnp.maximum(jnp.max(logits, axis=-1, keepdims=True), sink)
    e = jnp.exp(logits - m)
    return e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink - m))


def sdpa_xla(q, k, v, *, causal: bool, scale: float, mask=None,
             window: int = 0, sink=None):
    """Reference-semantics scaled dot-product attention, einsum form.
    q, k: (batch, heads, seq, head_dim), v: (batch, heads, seq, its own
    head_dim); `mask` (batch, seq, seq) bool, where given, is the
    positions each row attends (a learned selection, causal already);
    `window` > 0 keeps a causal row's nearest `window` keys, its own among
    them; `sink` (heads,) joins every row's denominator."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if mask is not None:
        logits = jnp.where(mask[:, None], logits, -1e30)
    elif causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        ones = jnp.ones((s_q, s_k), dtype=bool)
        mask = jnp.tril(ones, k=s_k - s_q)
        if window:
            mask &= ~jnp.tril(ones, k=s_k - s_q - window)
        logits = jnp.where(mask, logits, -1e30)
    probs = softmax_with_sink(
        logits, None if sink is None else sink[None, :, None, None]
    ).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def eva_attention_xla(front: AttentionFrontEnd, weights, q, k, v,
                      scale: float):
    """The training-shaped core under `summary_chunk`, in XLA: q, k, v
    (batch, seq, heads * head_dim), k rotated. The windows are a batch
    dimension for the exact keys (causal inside a window); every row also
    scores every chunk's summary, masked to the chunks of the windows
    before its own; one softmax over both, float32."""
    b, s, e = q.shape
    H, W, C = front.num_heads, front.window, front.summary_chunk
    pad = -s % W
    if pad:
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (q, k, v))
    nw, nc = (s + pad) // W, (s + pad) // C
    with jax.named_scope("eva.summarise"):
        ksum, vsum = front.summaries(
            weights, k.reshape(b, nc, C, e), v.reshape(b, nc, C, e))
    with jax.named_scope(front.attend_scope):
        qh, kh, vh = (t.reshape(b, nw, W, H, -1) for t in (q, k, v))
        ksum, vsum = (t.reshape(b, nc, H, -1) for t in (ksum, vsum))
        exact = jnp.einsum("bwqhd,bwkhd->bwhqk", qh, kh,
                           preferred_element_type=jnp.float32) * scale
        exact = jnp.where(jnp.tril(jnp.ones((W, W), bool)), exact, -1e30)
        summ = jnp.einsum("bwqhd,bchd->bwhqc", qh, ksum,
                          preferred_element_type=jnp.float32) * scale
        closed = (jnp.arange(nc)[None] // (W // C)) < jnp.arange(nw)[:, None]
        summ = jnp.where(closed[None, :, None, None], summ, -1e30)
        probs = jax.nn.softmax(jnp.concatenate([exact, summ], axis=-1),
                               axis=-1).astype(q.dtype)
        out = (jnp.einsum("bwhqk,bwkhd->bwqhd", probs[..., :W], vh)
               + jnp.einsum("bwhqc,bchd->bwqhd", probs[..., W:], vsum))
    return out.reshape(b, s + pad, -1)[:, :s]


def _mha_forward(p: MultiHeadAttentionParams, inputs, weights, state, ctx):
    front = p.front
    H = front.num_heads
    q, k, v = front.qkv(ctx, weights, *inputs)  # positions fourth, with RoPE
    scale = 1.0 / math.sqrt(front.head_dim)
    if front.summary_chunk:
        if not p.causal:
            raise NotImplementedError(
                "attention with chunk summaries is causal self-attention")
        out = eva_attention_xla(front, weights, q, k, v, scale)
        return [front.output(ctx, weights, out, inputs[0])], state
    group = H // front.kv_heads
    impl = p.impl
    mask = None
    if front.index:
        # the training-shaped form of a learned selection: dense
        # attention under the selection as a mask. Quadratic in seq: the
        # graph a model is built and checked as, not a long-context path
        from ..kernels.sparse_selection import causal_selection_mask

        if not p.causal or impl == "ring":
            raise NotImplementedError(
                "attention with an indexer is causal self-attention "
                "through the einsum core (impl 'xla' or 'flash', which "
                "falls back to it)")
        qi, ki, wt = front.index_inputs(ctx, weights, inputs[0], inputs[3])
        with jax.named_scope("dsa.topk"):
            mask = causal_selection_mask(qi, wt, ki, front.index.topk)
        impl = "xla"
    if front.window and not p.causal:
        raise NotImplementedError(
            "attention with a window is causal self-attention")
    repeat_kv = group > 1 and front.plain_core and impl == "flash"
    if repeat_kv:
        # grouped heads of one size over the whole past: the keys and
        # values of a KV head repeated for the `group` query heads that
        # read it, in the projections' packed layout, in front of the
        # unchanged packed kernels (query head i reads KV head i // group,
        # so a head-parallel shard's keys stay its own); the repeat's
        # transpose sums dK and dV over the group. The kernel then reads
        # `group` times the K and V it needs: the kernel-native form, block
        # index maps that read head i // group, is still missing
        def repeat(t):
            b, s, _ = t.shape
            t = t.reshape(b, s, front.kv_heads, 1, front.head_dim)
            return jnp.broadcast_to(
                t, (b, s, front.kv_heads, group, front.head_dim)
            ).reshape(b, s, front.q_width)

        with jax.named_scope("gqa.repeat"):
            k, v = repeat(k), repeat(v)
    elif (group > 1 or not front.plain_core) and impl != "xla":
        # the packed and ring kernels select q, k and v heads by one lane
        # offset and attend a row's whole past: one head count (but for
        # the repeat above), one head size, no band in their tile classes,
        # no sink. Such a layer takes the einsum
        from ..kernels.dispatch import warn_reference

        warn_reference("multihead_attention", tuple(q.shape),
                       f"{front.kv_heads} KV heads of {front.head_dim} / "
                       f"{front.v_head_dim} under {H} query heads, window "
                       f"{front.window}, sink {front.sink}: the {impl} "
                       f"kernels keep one head count and size and attend "
                       f"the whole past")
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
        attend = per_shard(
            functools.partial(flash_attention_packed,
                              num_heads=H // shards_of(ctx.mesh, head_ax),
                              causal=p.causal, scale=scale),
            ctx.mesh, (spec, spec, spec), spec)
        # the scope the grouped layer's core has on every path; an
        # ungrouped layer's call and its compiled text stay as they were
        with (jax.named_scope(front.attend_scope) if repeat_kv
              else contextlib.nullcontext()):
            out = attend(q, k, v)
        return [front.output(ctx, weights, out, inputs[0])], state

    def split_heads(x, heads):
        b, s, e = x.shape
        x = x.reshape(b, s, heads, e // heads).transpose(0, 2, 1, 3)
        # query head i reads KV head i // group
        return x if heads == H else jnp.repeat(x, group, axis=1)

    q = split_heads(q, H)
    k, v = split_heads(k, front.kv_heads), split_heads(v, front.kv_heads)
    if impl == "ring":
        from ..parallel.ring_attention import ring_attention

        out = ring_attention(q, k, v, causal=p.causal, scale=scale,
                             mesh=ctx.mesh,
                             overlap=getattr(ctx, "overlap_collectives", True))
    else:
        with jax.named_scope(front.attend_scope):
            out = sdpa_xla(q, k, v, causal=p.causal, scale=scale, mask=mask,
                           window=front.window,
                           sink=weights["sink"] if front.sink else None)
    b, _, s, _ = out.shape
    out = out.transpose(0, 2, 1, 3).reshape(b, s, front.o_width)
    return [front.output(ctx, weights, out, inputs[0])], state


def _mha_flops(p: MultiHeadAttentionParams, in_shapes, out_shapes):
    q, k, v = in_shapes[:3]
    b, sq, sk = q[0], q[1], k[1]
    attn = (2.0 * b * p.num_heads * sq * sk
            * (p.front.head_dim + p.front.v_head_dim))
    if p.front.index:
        attn += (2.0 * b * sq * sk * p.front.index.n_heads
                 * p.front.index.head_dim)
    return p.front.linear_flops(b, sq, sk, q[2], k[2], v[2]) + attn


def _mha_decode_layer(layer, ctx):
    """A causal self-attention layer serves through the incremental op over
    the paged pool of its group (global or window), or over the contiguous
    region: the trained layer's front end goes to the decode op whole."""
    from .inc_attention import (
        IncMultiHeadAttentionParams, PagedIncMultiHeadAttentionParams,
    )

    p = layer.params
    if not p.causal:
        raise ValueError(
            f"{layer.name}: serving decode requires causal attention "
            f"(non-causal layers see future tokens the cache does not hold "
            f"yet)")
    if not (layer.inputs[0] is layer.inputs[1] is layer.inputs[2]):
        raise ValueError(
            f"{layer.name}: serving decode supports self-attention only "
            f"(q, k, v must be one tensor)")
    if p.kdim not in (0, p.embed_dim) or p.vdim not in (0, p.embed_dim):
        raise ValueError(
            f"{layer.name}: kdim/vdim != embed_dim not supported in the "
            f"decode graph")
    if not ctx.paged:
        if p.front.selected(ctx.max_seq):
            raise NotImplementedError(
                f"{layer.name}: attention under a learned selection is "
                f"served from the paged pool only (kv_layout='paged')")
        if p.front.summary_chunk:
            raise NotImplementedError(
                f"{layer.name}: attention beside chunk summaries is served "
                f"from the paged pool only (kv_layout='paged')")
        return (OT.OP_INC_MULTIHEAD_ATTENTION,
                IncMultiHeadAttentionParams(p.front, ctx.max_seq,
                                            impl=ctx.impl,
                                            cache_dtype=ctx.at_rest),
                ("positions",))
    windowed = bool(p.front.window)
    both = bool(p.front.summary_chunk)  # leaves in both cache groups
    return (OT.OP_PAGED_INC_MULTIHEAD_ATTENTION,
            PagedIncMultiHeadAttentionParams(
                p.front, ctx.max_seq, ctx.block_size,
                ctx.window_blocks if windowed and not both else ctx.blocks,
                impl=ctx.impl, cache_dtype=ctx.at_rest, chunk_from=ctx.slots,
                window_blocks=ctx.window_blocks if both else 0),
            ("positions", "page_table", "page_table_w") if both else
            ("positions", "page_table_w" if windowed else "page_table"))


register_op(
    OpDef(OT.OP_MULTIHEAD_ATTENTION, _mha_infer, _mha_forward, _mha_weights,
          _mha_flops, decode_layer=_mha_decode_layer)
)
