"""Latent attention (DeepSeek-V2's multi-head latent attention), with a
learned sparse selection (DeepSeek-V3.2's lightning indexer) or without
one (Mistral-Small-4's: a row attends its whole past), as two ops over one
front end.

`LatentFrontEnd` owns what both share: the weights and their names, the
low-rank query, the compressed key-value row [cKV ; k^R] with its one
rotary key for all heads, the YaRN frequencies, the softmax scale, the
query's position-dependent scale where the model has one, and, as an
optional part (`index`, a LatentIndexer), the indexer's queries, key and
head weights. The equations are written out in
models/deepseek_v32_reference.py (with the indexer) and
models/mistral_small4_reference.py (without).

- OP_LATENT_ATTENTION, the training-shaped op on (batch, seq, hidden): the
  expanded form. Keys and values of every head are made from cKV, the
  indexer's top-k is a dense mask. Quadratic in seq: it is the graph a
  model is built and checked as, not a long-context path.
- OP_PAGED_LATENT_ATTENTION, the decode op on (rows, 1, hidden): the
  absorbed form over a paged latent cache. A token's cache is one row of
  kv_lora_rank + rope numbers in `pool_c` (stored 128-aligned:
  `LatentFrontEnd.cache_row_widths`) and one indexer key in `pool_i`,
  both (num_blocks, block_size, width), both under the one page table
  every layer shares. W_uk is folded into the query and W_uv applied after
  the weighted sum, so no per-head key or value is ever made. Under an
  indexer each row scores its cached indexer keys and attends the topk
  largest only: a slot's row scores them in the paged indexer kernel
  (kernels/sparse_selection.paged_index_scores, on one TPU) and gathers
  those rows of `pool_c`, a chunk's rows run dense over their shared
  context under the selection as a mask (kernels/sparse_latent_attention.py;
  the chunk's scores, the top-k and the row gather are XLA's). Without
  one there is no `pool_i` and no `sel_rows`: a slot's row reads every
  latent row of its history, each once as key and as value, in the paged
  latent kernel (kernels/paged_latent_attention.paged_latent_decode, on
  one TPU), and a chunk's rows run dense under the causal mask their
  positions give. The record of the last call is then `attended`, the
  layer's output at the slots' rows in float32 (an average over a whole
  history is what a model's logits see least of: a comparison reads it
  here, from the step's own program).

Rows of the decode op: the first `chunk_from` are the serving engine's
slots, each with its own page-table row. Rows past them, if a call has
any, are the tokens of ONE prefill chunk and share the page-table row of
the first of them (serving/engine.py lays a chunk step out so): their keys
are gathered once for all of them. Every row's cache rows are written
before any row reads, so a chunk's row sees the chunk's earlier rows.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..fftype import DataType, OperatorType as OT
from ..kernels.sparse_selection import causal_selection_mask
from .attention import (
    SELECTION_CANNOT, layer_norm, proj, rope_half, rope_pairs,
)
from .base import (
    BY_BLOCK, HANDOFF, LAST_CALL, QUERIES, DecodeState, OpDef, StateLeaf,
    WeightSpec, register_op,
)
from .core import rms_norm

# what a latent pool with no selection cannot follow (DecodeState.cannot):
# nothing of a selection's, only what the decode op itself does not do yet
WHOLE_HISTORY_CANNOT = dict.fromkeys(
    (HANDOFF, QUERIES),
    "latent attention ({layer}, ...): its cache is one pool of latent rows "
    "a layer, which the KV handoff (blocks of keys and of values) does not "
    "carry, and its decode op takes single-query rows only")


@dataclass(frozen=True)
class LatentIndexer:
    """DeepSeek-V3.2's lightning indexer on a latent layer: `n_heads`
    queries of `head_dim` projected from the QUERY LATENT, their weights
    and one key of `head_dim` a token (LayerNorm with a bias) from the
    hidden state, the first rope lanes of each rotated at the layer's
    frequencies (half-rotation form); a row attends the `topk` earlier
    tokens of largest sum_j w_j relu(q_j . k). (A grouped layer's is
    ops/attention.Indexer.)"""

    n_heads: int
    head_dim: int
    topk: int
    norm_eps: float = 1e-6


@dataclass(frozen=True)
class LatentFrontEnd:
    embed_dim: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    # the learned selection, or None: a row attends its whole past
    index: Optional[LatentIndexer] = None
    rope_theta: float = 10000.0
    # YaRN: (factor, original_max_position_embeddings, beta_fast,
    # beta_slow, mscale_all_dim), or None for plain frequencies
    rope_scaling: Optional[tuple] = None
    norm_eps: float = 1e-6
    # (beta, original_max_position_embeddings), or None: the query of
    # position t is multiplied by 1 + beta ln(1 + floor(t / original))
    # (Mistral-Small-4's `llama_4_scaling_beta`)
    query_scale: Optional[tuple] = None

    # what the block pool holds of a token in a layer
    @property
    def latent_row(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row_widths(self) -> dict:
        """{pool leaf: width of a token's row in it}. The latent row is
        stored in a row of the next multiple of 128 (640 for the
        published 576), zeros behind it: a TPU lays out an array whose
        last dimension is no multiple of its 128 lanes with another
        dimension innermost, and every step then copies the whole pool
        into the row layout and back (15 ms a step of the published
        configuration: PERF.md section 6, PR 31)."""
        widths = {"pool_c": -(-self.latent_row // 128) * 128}
        if self.index is not None:
            widths["pool_i"] = self.index.head_dim
        return widths

    @property
    def scale(self) -> float:
        s = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.rope_scaling:
            m = (0.1 * self.rope_scaling[4] * math.log(self.rope_scaling[0])
                 + 1.0)
            s *= m * m
        return s

    def inv_freq(self) -> np.ndarray:
        """The rotary frequencies (rope / 2,), YaRN's where scaled."""
        dim, theta = self.qk_rope_head_dim, self.rope_theta
        freqs = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64)
                                 / dim))
        if not self.rope_scaling:
            return freqs.astype(np.float32)
        factor, orig, fast, slow, _ = self.rope_scaling

        def correction_dim(rotations):
            return (dim * math.log(orig / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(correction_dim(fast)), 0)
        high = min(math.ceil(correction_dim(slow)), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                       / (high - low), 0.0, 1.0)
        return (freqs / factor * ramp + freqs * (1.0 - ramp)).astype(
            np.float32)

    def weight_specs(self, in_dim: int):
        H, f = self.num_heads, DataType.DT_FLOAT
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        specs = [
            WeightSpec("wq_a", (in_dim, self.q_lora_rank), f, "normal"),
            WeightSpec("q_norm", (self.q_lora_rank,), f, "ones"),
            WeightSpec("wq_b", (self.q_lora_rank, H * (dn + dr)), f,
                       "normal"),
            WeightSpec("wkv_a", (in_dim, self.latent_row), f, "normal"),
            WeightSpec("kv_norm", (self.kv_lora_rank,), f, "ones"),
            WeightSpec("wkv_b", (self.kv_lora_rank, H * (dn + dv)), f,
                       "normal"),
            WeightSpec("wo", (H * dv, self.embed_dim), f, "normal"),
        ]
        if self.index is not None:
            nI, dI = self.index.n_heads, self.index.head_dim
            specs += [
                WeightSpec("wi_q", (self.q_lora_rank, nI * dI), f, "normal"),
                WeightSpec("wi_k", (in_dim, dI), f, "normal"),
                WeightSpec("wi_k_norm", (dI,), f, "ones"),
                WeightSpec("wi_k_bias", (dI,), f, "zeros"),
                WeightSpec("wi_w", (in_dim, nI), f, "normal"),
            ]
        return specs

    @property
    def kernels(self) -> tuple:
        return ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo") + (
            ("wi_q", "wi_k", "wi_w") if self.index is not None else ())

    def project(self, ctx, weights, x, positions):
        """Everything a token gives before attention, from x (.., hidden)
        at positions (..): q_nope (.., H, nope), q_rope (.., H, rope)
        rotated, both under the query's scale of the position where the
        model has one, ckv (.., latent) normalised, kr (.., rope) rotated,
        and the indexer's (qi (.., nI, dI), ki (.., dI), wt (.., nI)
        float32), None without one."""
        H, dn, dr = (self.num_heads, self.qk_nope_head_dim,
                     self.qk_rope_head_dim)
        lead = x.shape[:-1]
        angles = (positions.astype(jnp.float32)[..., None]
                  * jnp.asarray(self.inv_freq()))
        with jax.named_scope("mla.q"):
            cq = rms_norm(proj(ctx, x, weights["wq_a"], None),
                          weights["q_norm"], self.norm_eps)
            q = proj(ctx, cq, weights["wq_b"], None).reshape(
                lead + (H, dn + dr))
            if self.query_scale is not None:
                # a scalar a position: before or after the rotation is
                # the same; float32 arithmetic, one cast back
                beta, original = self.query_scale
                a = 1.0 + beta * jnp.log1p(
                    (positions // original).astype(jnp.float32))
                q = (q.astype(jnp.float32)
                     * a[..., None, None]).astype(q.dtype)
            q_nope = q[..., :dn]
            q_rope = rope_pairs(q[..., dn:], angles[..., None, :])
        with jax.named_scope("mla.kv"):
            kv = proj(ctx, x, weights["wkv_a"], None)
            ckv = rms_norm(kv[..., :self.kv_lora_rank], weights["kv_norm"],
                           self.norm_eps)
            kr = rope_pairs(kv[..., self.kv_lora_rank:], angles)
        if self.index is None:
            return q_nope, q_rope, ckv, kr, None
        with jax.named_scope("dsa.index"):
            nI, dI = self.index.n_heads, self.index.head_dim
            qi = proj(ctx, cq, weights["wi_q"], None).reshape(
                lead + (nI, dI))
            qi = jnp.concatenate(
                [rope_half(qi[..., :dr], angles[..., None, :]),
                 qi[..., dr:]], axis=-1)
            ki = layer_norm(proj(ctx, x, weights["wi_k"], None),
                             weights["wi_k_norm"], weights["wi_k_bias"],
                             self.index.norm_eps)
            ki = jnp.concatenate([rope_half(ki[..., :dr], angles),
                                  ki[..., dr:]], axis=-1)
            wt = (proj(ctx, x, weights["wi_w"], None).astype(jnp.float32)
                  * (nI ** -0.5) * (dI ** -0.5))
        return q_nope, q_rope, ckv, kr, (qi, ki, wt)

    def up_weights(self, weights, dtype):
        """(W_uk (latent, H, nope), W_uv (latent, H, v)) of wkv_b."""
        w = weights["wkv_b"].astype(dtype).reshape(
            self.kv_lora_rank, self.num_heads,
            self.qk_nope_head_dim + self.v_head_dim)
        return w[..., :self.qk_nope_head_dim], w[..., self.qk_nope_head_dim:]

    def output(self, ctx, weights, o):
        with jax.named_scope("mla.out"):
            return proj(ctx, o, weights["wo"], None)

    def linear_flops(self, tokens: int, in_dim: int) -> float:
        H, ix = self.num_heads, self.index
        per_token = (
            in_dim * (self.q_lora_rank + self.latent_row
                      + (ix.head_dim + ix.n_heads if ix else 0))
            + self.q_lora_rank * (
                H * (self.qk_nope_head_dim + self.qk_rope_head_dim)
                + (ix.n_heads * ix.head_dim if ix else 0))
            + H * self.v_head_dim * self.embed_dim)
        return 2.0 * tokens * per_token


# ------------------------------------------------------------ training-shaped

@dataclass(frozen=True)
class LatentAttentionParams:
    front: LatentFrontEnd

    embed_dim = property(lambda self: self.front.embed_dim)
    num_heads = property(lambda self: self.front.num_heads)


def _latent_infer(p: LatentAttentionParams, in_shapes):
    x = in_shapes[0]
    return [(x[0], x[1], p.front.embed_dim)]


def _latent_weights(p: LatentAttentionParams, in_shapes):
    return p.front.weight_specs(in_shapes[0][-1])


def _latent_forward(p: LatentAttentionParams, inputs, weights, state, ctx):
    f = p.front
    x, positions = inputs
    b, s, _ = x.shape
    q_nope, q_rope, ckv, kr, index = f.project(ctx, weights, x, positions)
    if index is None:
        mask = jnp.tril(jnp.ones((s, s), bool))[None]
    else:
        qi, ki, wt = index
        with jax.named_scope("dsa.topk"):
            mask = causal_selection_mask(qi, wt, ki, f.index.topk)
    w_uk, w_uv = f.up_weights(weights, x.dtype)
    with jax.named_scope("mla.attend"):
        k_nope = jnp.einsum("bsc,chn->bshn", ckv, w_uk)
        scores = (jnp.einsum("bthn,bshn->bhts", q_nope, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bthr,bsr->bhts", q_rope, kr,
                               preferred_element_type=jnp.float32))
        scores = jnp.where(mask[:, None], scores * f.scale, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        v = jnp.einsum("bsc,chv->bshv", ckv, w_uv)
        o = jnp.einsum("bhts,bshv->bthv", probs, v)
    return [f.output(ctx, weights, o.reshape(b, s, -1))], state


def _latent_flops(p: LatentAttentionParams, in_shapes, out_shapes):
    b, s, d = in_shapes[0]
    f = p.front
    pairs = b * s * s
    indexed = f.index.n_heads * f.index.head_dim if f.index else 0
    return (f.linear_flops(b * s, d)
            + 2.0 * pairs * indexed
            + 2.0 * pairs * f.num_heads
            * (f.qk_nope_head_dim + f.qk_rope_head_dim + f.v_head_dim))


def _latent_decode_layer(layer, ctx):
    if not ctx.paged:
        raise NotImplementedError(
            f"{layer.name}: latent attention is served from the paged pool "
            f"only (kv_layout='paged')")
    return (OT.OP_PAGED_LATENT_ATTENTION,
            PagedLatentAttentionParams(
                layer.params.front, ctx.max_seq, ctx.block_size, ctx.blocks,
                chunk_from=ctx.slots, cache_dtype=ctx.at_rest),
            ("positions", "page_table"))


register_op(OpDef(OT.OP_LATENT_ATTENTION, _latent_infer, _latent_forward,
                  _latent_weights, _latent_flops,
                  decode_layer=_latent_decode_layer))


# --------------------------------------------------------------------- decode

@dataclass(frozen=True)
class PagedLatentAttentionParams:
    front: LatentFrontEnd
    max_seq_len: int    # logical cache rows per slot
    block_size: int
    num_blocks: int     # physical pool blocks, block 0 = reserved scratch
    chunk_from: int     # rows from here on are one chunk's (module doc)
    cache_dtype: DataType = DataType.DT_FLOAT

    embed_dim = property(lambda self: self.front.embed_dim)
    num_heads = property(lambda self: self.front.num_heads)

    @property
    def blocks_per_slot(self) -> int:
        return -(-self.max_seq_len // self.block_size)

    @property
    def selected(self) -> int:
        """Positions a row attends at the most; 0: all it has."""
        if self.front.index is None:
            return 0
        return min(self.front.index.topk,
                   self.blocks_per_slot * self.block_size)


def _paged_latent_infer(p: PagedLatentAttentionParams, in_shapes):
    x, positions, page_table = in_shapes
    if x[1] != 1:
        raise NotImplementedError(
            f"paged latent attention takes single-query rows (rows, 1, "
            f"hidden), got q_len {x[1]}: a prefill chunk rides as rows "
            f"past the slots (speculative verification is not served)")
    if page_table[-1] != p.blocks_per_slot:
        raise ValueError(
            f"page_table width {page_table[-1]} != blocks_per_slot "
            f"{p.blocks_per_slot}")
    return [(x[0], 1, p.front.embed_dim)]


def _paged_latent_state(p: PagedLatentAttentionParams) -> DecodeState:
    pools = tuple(StateLeaf(name, BY_BLOCK, (width,), p.cache_dtype)
                  for name, width in p.front.cache_row_widths.items())
    if p.front.index is None:
        # what the layer gave the slots' rows in the last call: a row here
        # is an average over its whole history, which a model's logits may
        # hardly see, so whoever compares this layer with another
        # implementation needs its output itself
        out = StateLeaf("attended", LAST_CALL, (p.front.embed_dim,),
                        DataType.DT_FLOAT)
        return DecodeState(
            pools + (out,), slots=p.chunk_from, blocks=p.num_blocks,
            block_size=p.block_size,
            chunk_as_rows=lambda mesh, itemsize: True,
            cannot=WHOLE_HISTORY_CANNOT)
    # the positions the slots' rows attended in the last call (-1 where a
    # row had fewer): selection is discontinuous, so whoever compares this
    # layer with another implementation needs the choice itself
    sel = StateLeaf("sel_rows", LAST_CALL, (p.selected,), DataType.DT_INT32)
    return DecodeState(
        pools + (sel,), slots=p.chunk_from, blocks=p.num_blocks,
        block_size=p.block_size, selected=p.selected,
        chunk_as_rows=lambda mesh, itemsize: True, cannot=SELECTION_CANNOT)


def _paged_latent_forward(p: PagedLatentAttentionParams, inputs, weights,
                          state, ctx):
    from ..kernels import paged_latent_attention as pla
    from ..kernels import sparse_latent_attention as sla
    from .inc_attention import _call_gate

    f = p.front
    x, positions, page_table = inputs
    rows = x.shape[0]
    x = x[:, 0]
    positions = positions[:, 0].astype(jnp.int32)
    page_table = page_table.astype(jnp.int32)
    live = (positions >= 0) & (positions < p.max_seq_len)
    pos = jnp.where(live, positions, -1)  # -1: attends nothing
    q_nope, q_rope, ckv, kr, index = f.project(
        ctx, weights, x, jnp.maximum(pos, 0))

    # write this call's rows before any row reads; a dead row writes
    # zeros into the scratch block (ops/inc_attention.py's rule)
    bs = p.block_size
    pos_c = jnp.maximum(pos, 0)
    phys = jnp.take_along_axis(page_table, (pos_c // bs)[:, None],
                               axis=1)[:, 0]
    phys = jnp.where(live, phys, 0)
    offset = jnp.where(live, pos_c % bs, 0)
    pool_c = weights["pool_c"]
    pad = jnp.zeros((rows, pool_c.shape[-1] - f.latent_row), ckv.dtype)
    latent = jnp.where(live[:, None], jnp.concatenate([ckv, kr, pad], -1),
                       0.0)
    pool_c = pool_c.at[phys, offset].set(latent.astype(pool_c.dtype))
    new_state = {"pool_c": pool_c}
    if index is not None:
        qi, ki, wt = index
        pool_i = weights["pool_i"].at[phys, offset].set(
            jnp.where(live[:, None], ki, 0.0).astype(
                weights["pool_i"].dtype))
        new_state["pool_i"] = pool_i

    w_uk, w_uv = f.up_weights(weights, x.dtype)
    with jax.named_scope("mla.q"):
        q = jnp.concatenate(
            [jnp.einsum("rhn,chn->rhc", q_nope, w_uk), q_rope,
             jnp.zeros(q_rope.shape[:-1] + pad.shape[-1:], q_rope.dtype)],
            axis=-1)

    n = min(rows, p.chunk_from)  # the slots' rows; the rest is one chunk
    chunk = rows > n
    mask = None  # of a chunk's rows: with no selection, the causal one
    if index is not None:
        with jax.named_scope("dsa.index"):
            scores = sla.index_scores_rows(qi[:n], wt[:n], pool_i,
                                           page_table[:n], pos[:n],
                                           call_gate=_call_gate(1, ctx.mesh))
            if chunk:
                index_c = sla.index_scores_chunk(qi[n:], wt[n:], pool_i,
                                                 page_table[n], pos[n:])
        with jax.named_scope("dsa.topk"):
            sel, valid = sla.select_topk(scores, f.index.topk)
            if chunk:
                mask = sla.selection_mask(index_c, f.index.topk)
    with jax.named_scope("mla.attend"):
        if index is None:
            o = pla.attend_rows(q[:n], pool_c, page_table[:n], pos[:n],
                                latent_dim=f.kv_lora_rank, scale=f.scale,
                                call_gate=_call_gate(1, ctx.mesh))
        else:
            o = sla.attend_selected(q[:n], pool_c, page_table[:n], sel,
                                    valid, latent_dim=f.kv_lora_rank,
                                    scale=f.scale)
        if chunk:
            o = jnp.concatenate([o, sla.attend_chunk(
                q[n:], pool_c, page_table[n], mask, pos[n:],
                latent_dim=f.kv_lora_rank, scale=f.scale)], axis=0)
        o = jnp.einsum("rhc,chv->rhv", o, w_uv)
    out = f.output(ctx, weights, o.reshape(rows, 1, -1))
    if rows >= p.chunk_from:
        if index is None:
            new_state["attended"] = out[:n, 0].astype(jnp.float32)
        else:
            new_state["sel_rows"] = jnp.where(valid, sel, -1)
    return [out], new_state


def _paged_latent_flops(p: PagedLatentAttentionParams, in_shapes,
                        out_shapes):
    rows, _, d = in_shapes[0]
    f = p.front
    cached = p.blocks_per_slot * p.block_size
    indexed = f.index.n_heads * f.index.head_dim if f.index else 0
    # with no selection a row attends its cached rows, not `selected`
    return (f.linear_flops(rows, d)
            + 2.0 * rows * cached * indexed
            + 2.0 * rows * (p.selected or cached) * f.num_heads
            * (f.latent_row + f.kv_lora_rank))


register_op(OpDef(OT.OP_PAGED_LATENT_ATTENTION, _paged_latent_infer,
                  _paged_latent_forward, _latent_weights,
                  _paged_latent_flops, state=_paged_latent_state,
                  state_leaves=dict(pool_c=BY_BLOCK, pool_i=BY_BLOCK,
                                    sel_rows=LAST_CALL, attended=LAST_CALL)))
