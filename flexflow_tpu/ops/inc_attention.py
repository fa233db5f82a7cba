"""Incremental (decode-phase) multi-head self-attention over a KV cache.

The serving engine's core op (serving/): the reference snapshot predates
FlexFlow's serving rewrite — this is its IncMultiHeadSelfAttention recast
TPU-natively. Where training attention (ops/attention.py) recomputes K/V
for the whole sequence every step, the decode op threads a **first-class
stateful parallel tensor** per layer: `cache_k`/`cache_v`, shape
(slots, max_seq_len + 1, embed_dim), declared as non-trainable weight
specs so the executor places them by the searched plan exactly like any
parameter — the slot dim rides the `data` axis with the batch, and a
head-parallel plan shards the feature dim over `model`, splitting each
chip's cache down to its own heads (the KV-cache placement Unity prices).

One forward call processes q_len tokens per slot at arbitrary,
per-element positions:

  - **position-indexed KV write**: the new K/V rows scatter into the cache
    at `positions` (a (slots, q_len) int32 input). Row `max_seq_len` is a
    scratch row — elements whose position is clipped there (empty slots,
    prefill padding) leave every real cache row untouched, which is how
    the continuous-batching engine runs a fixed-shape executable while
    slots sit at different sequence positions.
  - **masked read**: query row i of slot s attends cache rows
    [0, positions[s, i]] — intra-chunk causality during prefill falls out
    of the per-row positions; q_len=1 is the decode iteration.

The same two properties make q_len=K+1 the speculative VERIFY call
(serving/speculative.py): the drafter's K proposals plus the slot's last
token feed at positions [L..L+K], every row's write lands BEFORE the
masked read, and rows beyond a row's own position are invisible to it —
so rejected proposals need no device-side erase. The engine just rewinds
its host cursor: any stale row at or below a later call's query frontier
is overwritten by that call's own scatter before it becomes readable,
and rows beyond the frontier stay masked forever.

The front end (weights and their names, projections, head-parallel rule)
is ops/attention.py's `AttentionFrontEnd`, held as `params.front`: the
decode replay hands over a trained layer's whole, so its parameters
transfer to the decode graph by name. On TPU
the q_len=1 path routes through the Pallas decode kernel
(kernels/flash_attention.flash_decode_attention); CPU meshes use the
reference einsum so tier-1 exercises serving end-to-end. The paged op's
rows may be slots, or a prefill chunk's tokens one a row beside them,
each row with its own page-table row (serving/engine.py,
paged_rows_run_kernel below). What the op knows of them is
`chunk_from`, a fact of the decode graph: rows past it are ONE chunk
under one table row, and where the paged kernel serves the call they go
through one multi-query call that reads the chunk's context once
(kernels/flash_attention.paged_flash_chunk_attention).

A front end with an indexer (ops/attention.Indexer) over a cache of more
rows a slot than its `topk` turns the paged op into attention under a
learned selection (`_paged_selected_forward`): the pool's row is a
token's keys and values side by side (`pool_kv`) beside the indexer's key
(`pool_i`), every row scores its cached indexer keys and attends the
`topk` largest only, in ops/latent_attention.py's rows layout
(kernels/sparse_selection.py, kernels/sparse_grouped_attention.py: a
slot's row scores its keys in the paged indexer kernel, `paged_index_scores`,
on one TPU; a chunk's scores, the top-k and the selected rows' gather are
XLA's matmul, sort and gather).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ..fftype import DataType, OperatorType as OT
from .attention import AttentionFrontEnd, FrontEndFields
from .base import (
    BY_BLOCK, BY_POSITION, LAST_CALL, DecodeState, OpDef, StateLeaf,
    register_op,
)


@dataclass(frozen=True)
class IncMultiHeadAttentionParams(FrontEndFields):
    front: AttentionFrontEnd
    max_seq_len: int  # real cache rows; row max_seq_len is the scratch row
    impl: str = "auto"  # auto: flash decode on TPU (q_len=1), einsum else
    # what the cache rests in: build_decode_model fills it with the
    # model's compute dtype, so attention reads the cache as it lies
    # (K and V leave the projections in that dtype already)
    cache_dtype: DataType = DataType.DT_FLOAT


def _kernel_asked(impl: str) -> bool:
    """impl "flash" always asks for the Pallas decode kernel, "auto" asks
    on a TPU."""
    return impl == "flash" or (impl == "auto"
                               and jax.default_backend() == "tpu")


def _call_gate(q_len: int, mesh) -> str | None:
    """Why a call that asks for the decode kernel cannot have it, or None:
    multi-query calls (rectangular prefill chunks, speculative verify —
    the kernels are single-query) and multi-device meshes (GSPMD cannot
    partition a Mosaic kernel, and the decode kernels are not run per
    shard yet)."""
    if q_len != 1:
        return f"q_len {q_len} > 1 has no kernel"
    if mesh is not None and mesh.size > 1:
        return f"{mesh.size}-device mesh: kernel not run per shard"
    return None


def _use_decode_kernel(op: str, impl: str, q_shape, ctx) -> bool:
    """Whether this call runs the Pallas decode kernel: it is asked for
    (_kernel_asked) and the call can have it (_call_gate); an ask that
    takes the reference einsum instead says so on a TPU."""
    if not _kernel_asked(impl):
        return False
    gate = _call_gate(q_shape[1], ctx.mesh)
    if gate is not None:
        from ..kernels.dispatch import warn_reference

        warn_reference(op, tuple(q_shape), gate)
        return False
    return True


def _inc_mha_infer(p: IncMultiHeadAttentionParams, in_shapes):
    x, positions = in_shapes
    return [(x[0], x[1], p.embed_dim)]


def _inc_mha_state(p: IncMultiHeadAttentionParams) -> DecodeState:
    """The KV cache: stateful (non-trainable), zero-initialized, threaded
    functionally through the executor's state dict like BatchNorm stats. A
    handoff is asked of the paged layout only: its callers turn this one
    away before they ask."""
    return DecodeState(
        tuple(StateLeaf(name, BY_POSITION, (p.max_seq_len + 1, width),
                        p.cache_dtype)
              for name, width in (("cache_k", p.front.kv_width),
                                  ("cache_v", p.front.v_width))),
        cannot=p.front.cannot_follow)


def _self_attention_weights(p, in_shapes):
    x = in_shapes[0]
    return p.front.weight_specs(x[-1], x[-1], x[-1])


def _inc_mha_forward(p: IncMultiHeadAttentionParams, inputs, weights,
                     state, ctx):
    x, positions = inputs
    slots = x.shape[0]
    H = p.num_heads
    q, k, v = p.front.qkv(ctx, weights, x, x, x, positions)
    scale = 1.0 / math.sqrt(p.front.head_dim)

    ck, cv = weights["cache_k"], weights["cache_v"]
    positions = positions.astype(jnp.int32)
    # position-indexed write; >= max_seq_len clips to the scratch row, so
    # padded/empty elements never disturb live cache state
    write_pos = jnp.clip(positions, 0, p.max_seq_len)
    slot_idx = jnp.arange(slots, dtype=jnp.int32)[:, None]
    # scratch-bound elements write ZEROS, not their (garbage) K/V: a pad
    # element's hidden state can be NaN (OOB position-embedding gather
    # fills NaN), and although every read of the scratch row is masked,
    # softmax zeros times a NaN V row would still poison the live rows'
    # contraction — the cache must only ever hold finite values
    live = (positions >= 0) & (positions < p.max_seq_len)
    kw = jnp.where(live[..., None], k, 0.0)
    vw = jnp.where(live[..., None], v, 0.0)
    ck = ck.at[slot_idx, write_pos].set(kw.astype(ck.dtype))
    cv = cv.at[slot_idx, write_pos].set(vw.astype(cv.dtype))

    kv_heads = p.front.kv_heads
    if kv_heads != H or not p.front.plain_core:
        # the contiguous kernel keeps one head count and size and attends
        # a row's whole past (grouped keys and values, a window, a sink
        # are served by the paged kernel): the einsum repeats the heads
        # and masks the band. The cache holds every row all the same: a
        # window saves no memory in this layout
        from ..kernels.flash_attention import decode_attention_reference

        with jax.named_scope(p.front.attend_scope):
            out = decode_attention_reference(
                q, ck.astype(q.dtype), cv.astype(q.dtype), write_pos,
                num_heads=H, scale=scale, num_kv_heads=kv_heads,
                window=p.front.window,
                sink=weights["sink"] if p.front.sink else None)
    elif _use_decode_kernel("inc_multihead_attention", p.impl, q.shape,
                            ctx):
        from ..kernels.flash_attention import flash_decode_attention

        out = flash_decode_attention(
            q, ck.astype(q.dtype), cv.astype(q.dtype),
            write_pos[:, 0] + 1, num_heads=H, scale=scale)
    else:
        from ..kernels.flash_attention import decode_attention_reference

        out = decode_attention_reference(
            q, ck.astype(q.dtype), cv.astype(q.dtype), write_pos,
            num_heads=H, scale=scale)
    return [p.front.output(ctx, weights, out, x)], {"cache_k": ck,
                                                    "cache_v": cv}


def _inc_mha_flops(p: IncMultiHeadAttentionParams, in_shapes, out_shapes):
    return _decode_flops(p.front, in_shapes[0], p.max_seq_len + 1)


def _decode_flops(front: AttentionFrontEnd, x, cache_rows: int):
    """Four projections of the q_len new tokens + attention of each query
    against the full cache: the serving cost model prices the worst-case
    read, the kernels skip dead blocks at run time."""
    slots, q_len, d = x
    if front.window:
        cache_rows = min(cache_rows, front.window)
    attn = (2.0 * slots * front.num_heads * q_len * cache_rows
            * (front.head_dim + front.v_head_dim))
    return front.linear_flops(slots, q_len, q_len, d, d, d) + attn


register_op(OpDef(OT.OP_INC_MULTIHEAD_ATTENTION, _inc_mha_infer,
                  _inc_mha_forward, _self_attention_weights, _inc_mha_flops,
                  state=_inc_mha_state,
                  state_leaves=dict(cache_k=BY_POSITION, cache_v=BY_POSITION)))


# ===================================================================== paged
# Paged variant (vLLM/PagedAttention, SOSP '23): the per-layer KV cache is a
# shared BLOCK POOL `pool_k`/`pool_v` of shape (num_blocks, block_size,
# embed) plus a per-slot PAGE TABLE input (slots, blocks_per_slot) int32
# mapping logical block j of a slot to a physical pool block. The pool is
# still a first-class stateful parallel tensor (non-trainable weight spec):
# Unity places and prices it — the feature dim shards over `model` under a
# head-parallel plan exactly like the contiguous cache — and it is donated
# through the decode step like any state.
#
# Physical block 0 is the RESERVED SCRATCH BLOCK, the paged equivalent of
# the contiguous layout's scratch row `max_seq_len`: an element whose
# position clips out of [0, max_seq_len) writes ZEROS into block 0, so
# padded/empty elements never disturb a live block and the pool only ever
# holds finite values (same NaN-poisoning guard as the contiguous write).
# The block-sharing invariant is host-side: the engine's BlockManager
# guarantees (COW) that a physical block referenced by more than one page
# table is never the target of a write — the device op writes wherever the
# table points.


@dataclass(frozen=True)
class PagedIncMultiHeadAttentionParams(FrontEndFields):
    front: AttentionFrontEnd
    max_seq_len: int    # logical cache rows per slot (capacity)
    block_size: int     # pool rows per block
    num_blocks: int     # physical pool blocks, block 0 = reserved scratch
    impl: str = "auto"  # auto: paged flash decode on TPU (q_len=1)
    cache_dtype: DataType = DataType.DT_FLOAT  # as the contiguous op's
    # rows from here on, where a (rows, 1) call has any, are the tokens of
    # ONE prefill chunk and share the page-table row of the first of them
    # (the contract of ops/latent_attention.py's module docstring; a fact
    # of the decode graph, serving/decode_graph.py sets it to the slots).
    # None: every row stands alone under its own table row
    chunk_from: int | None = None
    # blocks of the window group's pool where the layer keeps leaves in
    # both groups (a front end with `summary_chunk`: `num_blocks` is then
    # the global group's); 0: all of its leaves are in one group, of
    # `num_blocks` blocks
    window_blocks: int = 0

    @property
    def blocks_per_slot(self) -> int:
        """Page-table width: logical blocks covering max_seq_len rows."""
        return -(-self.max_seq_len // self.block_size)

    @property
    def selected(self) -> int:
        """Positions a row attends at the most under the front end's
        learned selection, 0 for a plain layer
        (AttentionFrontEnd.selected)."""
        return self.front.selected(self.blocks_per_slot * self.block_size)

    @property
    def cache_row_widths(self) -> dict:
        return self.front.cache_row_widths(
            self.blocks_per_slot * self.block_size)


def paged_rows_run_kernel(p: PagedIncMultiHeadAttentionParams, mesh,
                          itemsize: int) -> bool:
    """Whether a (rows, 1) call of this op is served by the paged Pallas
    kernel, however many rows it has: the op's own gates (_kernel_asked,
    _call_gate) and the kernel wrapper's (paged_decode_gate), asked once
    and without a warning. `itemsize`: bytes of a pool element as the
    kernel reads it (the queries' dtype: a pool declared in it is read
    as it lies, a wider one is cast first). The
    serving engine chooses a chunk step's batch layout by this
    (serving/engine.py): single-query rows pay only where the kernel
    walks each row's pages; through the gather-and-einsum reference every
    row would gather a whole logical cache."""
    from ..kernels.flash_attention import paged_decode_gate

    return (_kernel_asked(p.impl) and _call_gate(1, mesh) is None
            and paged_decode_gate(
                p.blocks_per_slot * p.block_size, p.block_size,
                p.front.kv_width, p.front.kv_heads, itemsize,
                jax.default_backend() != "tpu", p.front.v_width) is None)


def _chunk_tiled_in_xla(front: AttentionFrontEnd) -> bool:
    """Whether a layer's chunk rows go through the tile loop in XLA
    (kernels/flash_attention.paged_chunk_attention_tiled): under a sink, or
    key and value heads of two sizes, which the chunk kernel does not take.
    A window it does (a first round and a second bound on its mask)."""
    return bool(front.sink) or front.v_head_dim != front.head_dim


def _chunk_gate(p: PagedIncMultiHeadAttentionParams, b: int,
                itemsize: int) -> str | None:
    """Why the b rows past `chunk_from` of a call the paged kernel serves
    cannot go through the multi-query chunk kernel, or None."""
    from ..kernels.flash_attention import paged_chunk_gate

    if _chunk_tiled_in_xla(p.front):
        return (f"key and value heads of {p.front.head_dim} / "
                f"{p.front.v_head_dim}, sink {p.front.sink}: the chunk "
                f"kernel slices one head size and has no sink")
    return paged_chunk_gate(
        b, p.blocks_per_slot * p.block_size, p.block_size,
        p.num_heads * p.front.head_dim, p.front.kv_width, p.num_heads,
        itemsize, jax.default_backend() != "tpu")


def paged_chunk_query_tile(p: PagedIncMultiHeadAttentionParams, mesh,
                           itemsize: int, b: int) -> int | None:
    """Query rows a tile of the chunk kernel takes where it serves a
    chunk of b rows riding a (rows, 1) call of this op, None where those
    rows go through the single-query kernel one by one (or no kernel
    serves the call at all). Each tile reads the chunk's context once, up
    to its own last row: the serving engine counts the context rows a
    chunk step reads by this (`kv_rows_walked`, `chunk_kernel_steps`)."""
    from ..kernels.flash_attention import _paged_chunk_query_tile

    if p.chunk_from is None or not paged_rows_run_kernel(p, mesh, itemsize):
        return None
    if _chunk_tiled_in_xla(p.front):
        # that loop reads the chunk's context once for all of its rows
        return b
    if _chunk_gate(p, b, itemsize) is not None:
        return None
    return _paged_chunk_query_tile(b, p.num_heads // p.front.kv_heads)[0]


def _paged_mha_infer(p: PagedIncMultiHeadAttentionParams, in_shapes):
    x, positions, page_table = in_shapes[:3]
    if p.front.summary_chunk and (x[1] != 1 or p.block_size
                                  % p.front.summary_chunk
                                  or p.front.window % p.block_size):
        raise NotImplementedError(
            f"paged attention beside chunk summaries takes single-query "
            f"rows (rows, 1, hidden) over blocks that hold whole chunks of "
            f"{p.front.summary_chunk} and divide the window of "
            f"{p.front.window}, got q_len {x[1]}, block_size {p.block_size}")
    if p.selected and x[1] != 1:
        raise NotImplementedError(
            f"paged attention under a learned selection takes "
            f"single-query rows (rows, 1, hidden), got q_len {x[1]}: a "
            f"prefill chunk rides as rows past the slots (speculative "
            f"verification is not served)")
    if page_table[-1] != p.blocks_per_slot:
        raise ValueError(
            f"page_table width {page_table[-1]} != blocks_per_slot "
            f"{p.blocks_per_slot} (= ceil({p.max_seq_len}/{p.block_size}))")
    return [(x[0], x[1], p.embed_dim)]


def _paged_mha_state(p: PagedIncMultiHeadAttentionParams) -> DecodeState:
    # the block pool: ONE tensor per layer shared by every slot (a block
    # mapped into N page tables is stored once — the prefix-sharing win),
    # so per-chip accounting counts it once, not per slot
    f = p.front
    windowed = int(bool(f.window))
    leaves = [StateLeaf(name, BY_BLOCK, (width,), p.cache_dtype,
                        group=windowed)
              for name, width in p.cache_row_widths.items()]
    if f.summary_chunk:
        # a summary row for every `summary_chunk` positions, for the whole
        # context: the global group's, beside the exact rows of the window
        leaves += [StateLeaf(name, BY_BLOCK, (f.kv_width,), p.cache_dtype,
                             every=f.summary_chunk)
                   for name in ("pool_ksum", "pool_vsum")]
    both = bool(f.summary_chunk)
    if p.selected:
        # the positions the slots' rows attended in the last call (-1
        # where a row had fewer), as ops/latent_attention.py keeps them
        leaves.append(StateLeaf("sel_rows", LAST_CALL, (p.selected,),
                                DataType.DT_INT32))
    # attention under a learned selection takes a chunk as rows only: it
    # gathers the chunk's keys once for all of them
    return DecodeState(
        tuple(leaves), slots=p.chunk_from or 0,
        blocks=p.num_blocks if both or not windowed else 0,
        window_blocks=(p.window_blocks if both
                       else p.num_blocks if windowed else 0),
        block_size=p.block_size, window=f.window, window_aligned=both,
        selected=p.selected,
        # a chunk rides as rows where a row is what the core takes (a
        # learned selection, summaries) or where the paged kernel serves
        chunk_as_rows=lambda mesh, itemsize: bool(
            p.selected or both or paged_rows_run_kernel(p, mesh, itemsize)),
        chunk_query_tile=(None if both
                          else partial(paged_chunk_query_tile, p)),
        rows_walk=(None if both or p.selected or windowed
                   else partial(paged_rows_run_kernel, p)),
        step_counts=f.step_counts if both else None,
        cannot=f.cannot_follow)


def _paged_mha_forward(p: PagedIncMultiHeadAttentionParams, inputs, weights,
                       state, ctx):
    x, positions, page_table = inputs[:3]
    slots = x.shape[0]
    H = p.num_heads
    kv_heads = p.front.kv_heads
    bs = p.block_size
    W = p.blocks_per_slot
    q, k, v = p.front.qkv(ctx, weights, x, x, x, positions)
    scale = 1.0 / math.sqrt(p.front.head_dim)
    if p.front.summary_chunk:
        return _paged_summary_forward(p, x, positions, page_table, inputs[3],
                                      q, k, v, weights, ctx)
    if p.selected:
        return _paged_selected_forward(p, x, positions, page_table, q, k, v,
                                       weights, ctx)

    pk, pv = weights["pool_k"], weights["pool_v"]
    positions = positions.astype(jnp.int32)
    page_table = page_table.astype(jnp.int32)
    live = (positions >= 0) & (positions < p.max_seq_len)
    # position → (physical block, in-block offset) through the page table;
    # dead elements route to the scratch block (0) and write zeros — see
    # the contiguous op's scratch-row rationale (NaN'd pad hidden states
    # must never reach the pool even though reads mask them)
    pos_c = jnp.clip(positions, 0, p.max_seq_len - 1)
    logical = pos_c // bs                       # (slots, q_len) in [0, W)
    offset = pos_c % bs
    phys = jnp.take_along_axis(page_table, logical, axis=1)
    phys = jnp.where(live, phys, 0)
    kw = jnp.where(live[..., None], k, 0.0)
    vw = jnp.where(live[..., None], v, 0.0)
    pk = pk.at[phys, offset].set(kw.astype(pk.dtype))
    pv = pv.at[phys, offset].set(vw.astype(pv.dtype))

    window = p.front.window
    sink = weights["sink"] if p.front.sink else None
    if _use_decode_kernel("paged_inc_multihead_attention", p.impl, q.shape,
                          ctx):
        from ..kernels.flash_attention import (
            paged_chunk_attention_tiled, paged_flash_chunk_attention,
            paged_flash_decode_attention,
        )

        pools = pk.astype(q.dtype), pv.astype(q.dtype)
        lengths = jnp.where(live[:, 0], pos_c[:, 0] + 1, 0)
        kw = dict(num_heads=H, scale=scale, num_kv_heads=kv_heads,
                  window=window)
        # the slots' rows, each under its own table row; where rows past
        # them are a chunk's and its kernel takes them, those under ONE
        # table row in one multi-query call (every row's K and V are in
        # the pool by now); a layer that kernel cannot tile (a sink, key
        # and value heads of two sizes) takes the chunk through the tile
        # loop in XLA, which reads its context once as well; else the
        # same single-query kernel, row by row
        n = slots if p.chunk_from is None else min(slots, p.chunk_from)
        tiled = _chunk_tiled_in_xla(p.front)
        if (n < slots and not tiled
                and _chunk_gate(p, slots - n, q.dtype.itemsize) is not None):
            n = slots
        with jax.named_scope(p.front.attend_scope):
            out = paged_flash_decode_attention(
                q[:n], *pools, page_table[:n], lengths[:n], sink=sink, **kw)
            if n < slots and not tiled:
                out = jnp.concatenate([out, paged_flash_chunk_attention(
                    q[n:], *pools, page_table[n], lengths[n:], **kw)])
            elif n < slots:
                out = jnp.concatenate([out, paged_chunk_attention_tiled(
                    q[n:], *pools, page_table[n], lengths[n:] - 1, sink=sink,
                    **kw)])
    else:
        # reference path (CPU tier-1 + the kernel's numerics oracle):
        # gather each slot's logical cache view from the pool, then run
        # the SAME masked einsum as the contiguous op — token identity
        # between the layouts reduces to the gather being the identity
        # on live rows (a window layer's table holds the scratch block
        # where its blocks fell behind the window: masked rows)
        kc = pk[page_table].reshape(slots, W * bs, -1).astype(q.dtype)
        vc = pv[page_table].reshape(slots, W * bs, -1).astype(q.dtype)
        from ..kernels.flash_attention import decode_attention_reference

        read_pos = jnp.where(live, pos_c, -1)
        with jax.named_scope(p.front.attend_scope):
            out = decode_attention_reference(
                q, kc, vc, read_pos, num_heads=H, scale=scale,
                num_kv_heads=kv_heads, window=window, sink=sink)
    return [p.front.output(ctx, weights, out, x)], {"pool_k": pk,
                                                    "pool_v": pv}


def _paged_selected_forward(p: PagedIncMultiHeadAttentionParams, x,
                            positions, page_table, q, k, v, weights, ctx):
    """The paged op under a learned selection (`p.selected` > 0), on
    single-query rows: every row writes its [k ; v] row and its indexer
    key, then scores the indexer keys its context cached and attends the
    `topk` largest only. A slot's row gathers those rows of `pool_kv`; the
    rows past `chunk_from` are one chunk's and run dense over their shared
    context under the selection as a mask (ops/latent_attention.py's
    layout, kernels/sparse_selection.py, sparse_grouped_attention.py)."""
    from ..kernels import sparse_grouped_attention as sga
    from ..kernels import sparse_selection as sel

    f, bs = p.front, p.block_size
    rows = x.shape[0]
    qi, ki, wt = f.index_inputs(ctx, weights, x, positions)
    # the pool's indexer row is lane-aligned, zeros behind the key
    # (AttentionFrontEnd.cache_row_widths): queries and key are filled up
    # alike, which adds nothing to q . k
    pad = weights["pool_i"].shape[-1] - f.index.head_dim
    qi = jnp.pad(qi[:, 0], ((0, 0), (0, 0), (0, pad)))
    ki, wt = jnp.pad(ki[:, 0], ((0, 0), (0, pad))), wt[:, 0]
    positions = positions[:, 0].astype(jnp.int32)
    page_table = page_table.astype(jnp.int32)
    live = (positions >= 0) & (positions < p.max_seq_len)
    pos = jnp.where(live, positions, -1)  # -1: attends nothing

    # write this call's rows before any row reads; a dead row writes
    # zeros into the scratch block (the plain op's rule)
    pos_c = jnp.maximum(pos, 0)
    phys = jnp.take_along_axis(page_table, (pos_c // bs)[:, None],
                               axis=1)[:, 0]
    phys = jnp.where(live, phys, 0)
    offset = jnp.where(live, pos_c % bs, 0)
    pool_kv, pool_i = weights["pool_kv"], weights["pool_i"]
    kv = jnp.where(live[:, None],
                   jnp.concatenate([k[:, 0], v[:, 0]], axis=-1), 0.0)
    pool_kv = pool_kv.at[phys, offset].set(kv.astype(pool_kv.dtype))
    pool_i = pool_i.at[phys, offset].set(
        jnp.where(live[:, None], ki, 0.0).astype(pool_i.dtype))

    n = rows if p.chunk_from is None else min(rows, p.chunk_from)
    chunk = rows > n  # the slots' rows come first; the rest is one chunk
    with jax.named_scope("dsa.index"):
        index = sel.index_scores_rows(qi[:n], wt[:n], pool_i,
                                      page_table[:n], pos[:n],
                                      call_gate=_call_gate(1, ctx.mesh))
        if chunk:
            index_c = sel.index_scores_chunk(qi[n:], wt[n:], pool_i,
                                             page_table[n], pos[n:])
    with jax.named_scope("dsa.topk"):
        picked, valid = sel.select_topk(index, p.selected)
        if chunk:
            mask = sel.selection_mask(index_c, p.selected)
    qh = q[:, 0].reshape(rows, p.num_heads, f.head_dim)
    kw = dict(kv_heads=f.kv_heads, scale=1.0 / math.sqrt(f.head_dim))
    with jax.named_scope("gsa.attend"):
        o = sga.attend_selected(qh[:n], pool_kv, page_table[:n], picked,
                                valid, **kw)
        if chunk:
            o = jnp.concatenate([o, sga.attend_chunk(
                qh[n:], pool_kv, page_table[n], mask, pos[n:], **kw)])
    out = f.output(ctx, weights, o.reshape(rows, 1, -1), x)
    new_state = {"pool_kv": pool_kv, "pool_i": pool_i}
    if n == weights["sel_rows"].shape[0]:
        new_state["sel_rows"] = jnp.where(valid, picked, -1)
    return [out], new_state


def _paged_summary_forward(p: PagedIncMultiHeadAttentionParams, x,
                           positions, table, table_w, q, k, v, weights, ctx):
    """The paged op beside chunk summaries (`front.summary_chunk` > 0), on
    single-query rows, a slot's or a prefill chunk's alike (each under its
    own table rows): every row writes its exact k and v into the window
    group's pool; a row that completes a chunk reads the chunk's rows back
    (cached ones and this call's) and writes their summary into the global
    group's pool, a row a chunk; then every row attends, under one softmax,
    the exact rows from its window's start on and the summaries of the
    windows before it. On a TPU the two sets are two calls of the paged
    decode kernel (the window's pages under a table shifted to its start;
    the summary pool as pages of block_size / chunk rows) merged by their
    log-sum-exp, and a chunk's rows three calls of the chunk kernel merged
    likewise; elsewhere the same two sets in jax.numpy, merged the same
    way."""
    f, bs = p.front, p.block_size
    W, C = f.window, f.summary_chunk
    rows = x.shape[0]
    pos = positions[:, 0].astype(jnp.int32)
    table, table_w = table.astype(jnp.int32), table_w.astype(jnp.int32)
    live = (pos >= 0) & (pos < p.max_seq_len)
    pos_c = jnp.where(live, pos, 0)
    lb, off = (pos_c // bs)[:, None], jnp.where(live, pos_c % bs, 0)

    def block_of(tbl, ok):  # dead rows write zeros into the scratch block
        return jnp.where(ok, jnp.take_along_axis(tbl, lb, axis=1)[:, 0], 0)

    pk, pv = weights["pool_k"], weights["pool_v"]
    phys = block_of(table_w, live)
    pk = pk.at[phys, off].set(
        jnp.where(live[:, None], k[:, 0], 0.0).astype(pk.dtype))
    pv = pv.at[phys, off].set(
        jnp.where(live[:, None], v[:, 0], 0.0).astype(pv.dtype))

    sk, sv = weights["pool_ksum"], weights["pool_vsum"]
    with jax.named_scope("eva.summarise"):
        # the chunk a row completes lies in one block (block_size % C == 0)
        # and this call's rows of it are in the pool by now
        done = live & (pos_c % C == C - 1)
        first = jnp.where(done, off - (C - 1), 0)

        def chunk_rows(pool):
            return jax.vmap(lambda b, o: jax.lax.dynamic_slice(
                pool, (b, o, 0), (1, C, pool.shape[-1]))[0])(phys, first)

        ksum, vsum = f.summaries(weights, chunk_rows(pk), chunk_rows(pv))
        gphys, goff = block_of(table, done), jnp.where(done, off // C, 0)
        sk = sk.at[gphys, goff].set(
            jnp.where(done[:, None], ksum, 0.0).astype(sk.dtype))
        sv = sv.at[gphys, goff].set(
            jnp.where(done[:, None], vsum, 0.0).astype(sv.dtype))

    window = pos_c // W
    n_exact = jnp.where(live, pos_c % W + 1, 0)
    n_sum = jnp.where(live, window * (W // C), 0)
    # the pages of a row's own window, as a table of their own
    pages = (window * (W // bs))[:, None] + jnp.arange(W // bs)[None]
    table_x = jnp.take_along_axis(
        table_w, jnp.minimum(pages, table_w.shape[1] - 1), axis=1)
    scale = 1.0 / math.sqrt(f.head_dim)
    from ..kernels.flash_attention import (
        paged_decode_attention_reference, paged_flash_chunk_attention,
        paged_flash_decode_attention,
    )

    kernel = _use_decode_kernel("paged_inc_multihead_attention", p.impl,
                                q.shape, ctx)
    kw = dict(num_heads=p.num_heads, scale=scale)
    exact, summed = (pk, pv), (sk, sv)

    def rows_attend(pools, tables, counts, at):
        """(output, lse (rows, heads)) of the rows `at` over the first
        `counts` rows of their logical caches: the paged decode kernel, or
        its jax.numpy form (the CPU path, and the kernel's oracle)."""
        if kernel:
            return paged_flash_decode_attention(
                q[at], *pools, tables[at], counts[at], return_lse=True, **kw)
        out, lse = paged_decode_attention_reference(
            q[at], *pools, tables[at], counts[at][:, None] - 1,
            return_lse=True, **kw)
        return out, lse[:, 0]

    def one_softmax(*sets):
        """Sets of keys, each its (output, lse), as the one softmax over
        all of them: a set's share of a row's head is exp(lse)."""
        share = jax.nn.softmax(jnp.stack([lse for _, lse in sets]), axis=0)
        share = jnp.repeat(share, f.head_dim, axis=-1)[:, :, None]
        return sum(o.astype(jnp.float32) * part
                   for (o, _), part in zip(sets, share)).astype(q.dtype)

    # the slots' rows, each under its own table rows. Where the kernel
    # serves, the rows past them are ONE chunk's under one table row a
    # group (`chunk_from`) and go through the multi-query kernel, which
    # reads their context once: the summaries, the window of the chunk's
    # first row, and the next window (the rows of a chunk that straddles
    # a boundary; no row otherwise, and a tile without a live row runs no
    # round)
    n = rows
    if kernel and p.chunk_from is not None:
        n = min(rows, p.chunk_from)
    with jax.named_scope(f.attend_scope):
        out = one_softmax(rows_attend(exact, table_x, n_exact, slice(n)),
                          rows_attend(summed, table, n_sum, slice(n)))
        if n < rows:
            first = window[n]
            sets = [paged_flash_chunk_attention(
                q[n:], *summed, table[n], n_sum[n:], return_lse=True, **kw)]
            for w in (first, first + 1):
                at = jnp.minimum(w * (W // bs) + jnp.arange(W // bs),
                                 table_w.shape[1] - 1)
                sets.append(paged_flash_chunk_attention(
                    q[n:], *exact, table_w[n][at],
                    jnp.where(window[n:] == w, n_exact[n:], 0),
                    return_lse=True, **kw))
            out = jnp.concatenate([out, one_softmax(*sets)])
    return [f.output(ctx, weights, out, x)], {
        "pool_k": pk, "pool_v": pv, "pool_ksum": sk, "pool_vsum": sv}


def _paged_mha_flops(p: PagedIncMultiHeadAttentionParams, in_shapes,
                     out_shapes):
    cached = p.blocks_per_slot * p.block_size
    if not p.selected:
        return _decode_flops(p.front, in_shapes[0], cached)
    rows, q_len, d = in_shapes[0]
    ix = p.front.index
    return (_decode_flops(p.front, in_shapes[0], p.selected)
            + 2.0 * rows * q_len * cached * ix.n_heads * ix.head_dim)


register_op(OpDef(OT.OP_PAGED_INC_MULTIHEAD_ATTENTION, _paged_mha_infer,
                  _paged_mha_forward, _self_attention_weights,
                  _paged_mha_flops,
                  state=_paged_mha_state,
                  state_leaves=dict(pool_k=BY_BLOCK, pool_v=BY_BLOCK,
                                    pool_kv=BY_BLOCK, pool_i=BY_BLOCK,
                                    pool_ksum=BY_BLOCK, pool_vsum=BY_BLOCK,
                                    sel_rows=LAST_CALL)))
