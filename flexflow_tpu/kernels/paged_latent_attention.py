"""Latent attention of decoding rows over their WHOLE cached history, read
from the paged latent pool: the decode path of latent attention with no
learned selection (Mistral-Small-4's; under DeepSeek-V3.2's indexer a row
reads 2,048 gathered rows instead, kernels/sparse_latent_attention.py).

A token's cache row is [cKV ; k^R ; zeros] (ops/latent_attention.py,
`LatentFrontEnd.cache_row_widths`) and a row's queries come absorbed: head
h's is [q^C_h W_uk,h ; q^R_h ; zeros], as wide as the cache row. So one
latent row serves every head twice over, as the key (all its lanes) and
as the value (its first `latent_dim` lanes), and is read from HBM once.

`paged_latent_decode` is the Pallas kernel, `paged_latent_decode_reference`
the same in jax.numpy (every page of a table row gathered, live or not:
the CPU serving path, a multi-device mesh's, and the kernel's oracle), and
`attend_rows` what the op calls: the kernel on one TPU where
`paged_latent_gate` passes, the reference elsewhere
(tests/test_paged_latent_attention.py holds the two to each other).

The kernel, as kernels/sparse_selection.paged_index_scores walks: one grid
step a row, the page table and the rows' lengths scalar-prefetched, the
pool left in HBM. The body copies the row's LIVE pages, whole pool rows of
(block, lanes), a round of _ROUND_ROWS keys at a time into one of two VMEM
buffers, the next round in flight while this one is used: (heads, lanes) @
(lanes, keys) on the MXU, the scale, the mask behind the row's position,
an online softmax in float32, and (heads, keys) @ (keys, latent_dim) into
the accumulator from the same buffer. A page past the length is never a
DMA. The grid runs in order and a row's last round starts the next row's
first, so only the call's first round waits for HBM with nothing to do.
Pool rows the walk reads hold finite numbers (the engine's pool starts as
zeros and takes what steps write): a masked key's weight is exactly 0 and
its value is not masked again.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import warn_reference

NEG = -1e30
_ROUND_ROWS = 2048  # keys a DMA round: 1.5 MB of 384-lane bf16 rows
# two rounds' keys may take this much of the 16 MiB a Mosaic kernel gets
# by default; the rest is the compiler's: a round's (heads, keys) float32
# scores, their exponentials, the accumulator
_ROUND_VMEM = 8 << 20


def _round_pages(width: int, block_size: int) -> int:
    return max(1, min(width, _ROUND_ROWS // block_size))


def paged_latent_decode_reference(q, pool_c, page_table, positions, *,
                                  latent_dim: int, scale: float):
    """sum_s p_s cKV_s (rows, heads, latent_dim) in q's dtype, p = softmax
    over s <= position of scale q . [cKV ; k^R]_s: q (rows, heads, lanes)
    absorbed, pool_c (blocks, block, lanes), page_table (rows, W),
    positions (rows,), negative = a dead row, which gives zeros. Every page
    of a table row is gathered, live or not; what lies behind a row's
    position is masked as key and as value (a dead page may hold
    anything)."""
    rows, W = page_table.shape
    bs = pool_c.shape[1]
    keys = pool_c[page_table].reshape(rows, W * bs, -1).astype(q.dtype)
    seen = jnp.arange(W * bs)[None, :] <= positions[:, None]
    keys = jnp.where(seen[..., None], keys, 0)
    scores = jnp.einsum("rhc,rsc->rhs", q, keys,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(seen[:, None, :], scores, NEG)
    top = jnp.max(scores, axis=-1, keepdims=True)
    probs = jnp.where(seen[:, None, :], jnp.exp(scores - top), 0.0)
    total = jnp.sum(probs, axis=-1, keepdims=True)
    acc = jnp.einsum("rhs,rsc->rhc", probs.astype(q.dtype),
                     keys[..., :latent_dim],
                     preferred_element_type=jnp.float32)
    return (acc / jnp.maximum(total, 1e-30)).astype(q.dtype)


def _paged_latent_kernel(tbl_ref, len_ref, q_ref, pool_hbm, o_ref, k_buf,
                         sem, base_ref, acc_ref, *, scale: float):
    """One row's walk (module docstring). q_ref (1, heads, lanes), o_ref
    (1, heads, latent_dim); k_buf (2, keys a round, lanes), one DMA
    semaphore a buffer, `base_ref`, the buffer the row's first round lies
    in, carried from grid step to grid step, and the float32 accumulator
    (heads, latent_dim)."""
    r = pl.program_id(0)
    rows, width = tbl_ref.shape
    bs = pool_hbm.shape[1]
    span = k_buf.shape[1]
    pages = span // bs
    heads, latent_dim = acc_ref.shape
    length = len_ref[r]

    def live_pages(row):
        return pl.cdiv(len_ref[row], bs)

    def rounds(row):
        return pl.cdiv(live_pages(row), pages)

    n_rounds = rounds(r)

    def first_page(c):
        # a width the round does not divide: its last round ends at the
        # table's end and holds a few pages of the round before again,
        # which the mask below leaves out, rather than run past the table
        return jnp.minimum(c * pages, width - pages)

    def copies(row, c, buf, act: str):
        """Start, or wait for, the DMAs of `row`'s round c: its live pages
        only (a loop, not `pages` copies of the body: the kernel is lowered
        once a layer in every bucket program)."""
        p0 = first_page(c)

        @pl.loop(0, jnp.minimum(live_pages(row) - p0, pages))
        def _page(p):
            dma = pltpu.make_async_copy(
                pool_hbm.at[tbl_ref[row, p0 + p]],
                k_buf.at[buf, pl.ds(pl.multiple_of(p * bs, bs), bs)],
                sem.at[buf])
            getattr(dma, act)()

    # a row's first round is started by the row before it, beside that
    # row's last round (the grid runs in order): the buffers alternate over
    # the whole call, and `base` is the one this row's round 0 lies in
    @pl.when(r == 0)
    def _origin():
        base_ref[0] = 0
        # rows of a page no round has copied yet are read as values under
        # a weight of 0: they have to be numbers
        k_buf[...] = jnp.zeros(k_buf.shape, k_buf.dtype)

    base = base_ref[0]
    nxt = jnp.minimum(r + 1, rows - 1)
    has_next = (r + 1 < rows) & (rounds(nxt) > 0)

    @pl.when((r == 0) & (n_rounds > 0))
    def _first():
        copies(r, 0, base, "start")

    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0]   # (heads, lanes)

    def round_(c, carry):
        top, total = carry
        buf = (base + c) % 2

        @pl.when(c + 1 < n_rounds)
        def _next():
            copies(r, c + 1, 1 - buf, "start")

        @pl.when((c + 1 == n_rounds) & has_next)
        def _next_row():
            copies(nxt, 0, 1 - buf, "start")

        copies(r, c, buf, "wait")
        keys = k_buf[buf]  # (span, lanes)
        scores = jax.lax.dot_general(
            q, keys.astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (heads, span)
        first = pl.multiple_of(first_page(c) * bs, bs)
        key_pos = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1) + first
        # behind the position, or a page the round before has counted
        seen = (key_pos < length) & (key_pos >= c * span)
        scores = jnp.where(seen, scores, NEG)
        new_top = jnp.maximum(top, scores.max(axis=-1, keepdims=True))
        probs = jnp.where(seen, jnp.exp(scores - new_top), 0.0)
        keep = jnp.exp(top - new_top)
        acc_ref[...] = acc_ref[...] * keep + jax.lax.dot_general(
            probs.astype(q.dtype), keys[:, :latent_dim].astype(q.dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return new_top, total * keep + probs.sum(axis=-1, keepdims=True)

    _, total = jax.lax.fori_loop(
        0, n_rounds, round_,
        (jnp.full((heads, 1), NEG, jnp.float32),
         jnp.zeros((heads, 1), jnp.float32)))

    @pl.when((n_rounds == 0) & has_next)
    def _dead_row():  # no last round to start the next row's beside
        copies(nxt, 0, base, "start")

    base_ref[0] = (base + n_rounds) % 2
    # a dead row ran no round: zeros, and nobody reads it
    o_ref[0] = (acc_ref[...] / jnp.maximum(total, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("latent_dim", "scale", "interpret"))
def _paged_latent_call(table, lengths, q, pool_c, *, latent_dim: int,
                       scale: float, interpret: bool):
    """The kernel launch (shapes already gated), jitted for the memory
    space constraint as `sparse_selection._paged_index_call` is."""
    rows, heads, lanes = q.shape
    W = table.shape[1]
    bs = pool_c.shape[1]
    span = _round_pages(W, bs) * bs
    if not interpret:
        # else XLA may park the pool in VMEM (PERF.md section 6, PR 26)
        pool_c = pltpu.with_memory_space_constraint(pool_c, pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rows,),
        in_specs=[
            pl.BlockSpec((1, heads, lanes), lambda r, tbl, ln: (r, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=pl.BlockSpec((1, heads, latent_dim),
                               lambda r, tbl, ln: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, span, lanes), pool_c.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((heads, latent_dim), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_latent_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, heads, latent_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_latent_decode",
    )(table, lengths, q, pool_c)


def paged_latent_gate(width: int, block_size: int, lanes: int,
                      latent_dim: int, heads: int,
                      itemsize: int) -> str | None:
    """Why the paged latent kernel cannot take a pool of this geometry on
    a TPU, or None where it can: a block must be whole (sublane, lane)
    tiles in the round's buffer, the pool's row and the value part of it
    whole 128-lane tiles, the heads whole sublane tiles of the scores, and
    two rounds of keys must sit in VMEM. Nothing here depends on how many
    rows a call has."""
    sublanes = 8 * max(1, 4 // itemsize)
    if block_size % sublanes != 0:
        return (f"block_size {block_size} % {sublanes} != 0: a page is no "
                f"whole tiles of the round's buffer")
    if lanes % 128 != 0 or latent_dim % 128 != 0 or latent_dim > lanes:
        return (f"latent rows of {lanes} lanes, {latent_dim} of them the "
                f"value: no whole 128-lane tiles")
    if heads % 8 != 0:
        return f"{heads} heads: no whole sublane tiles of scores"
    span = _round_pages(width, block_size) * block_size
    need = 2 * span * lanes * itemsize
    if need > _ROUND_VMEM:
        return (f"two rounds of {span} keys x {lanes} lanes take {need} "
                f"bytes of VMEM > {_ROUND_VMEM}")
    return None


def paged_latent_decode(q, pool_c, page_table, positions, *,
                        latent_dim: int, scale: float):
    """`paged_latent_decode_reference` by the Pallas kernel, whatever the
    backend (interpret mode off a TPU: the kernel's own tests)."""
    W, bs = page_table.shape[1], pool_c.shape[1]
    lengths = jnp.clip(positions.astype(jnp.int32) + 1, 0, W * bs)
    return _paged_latent_call(
        page_table.astype(jnp.int32), lengths, q, pool_c,
        latent_dim=latent_dim, scale=float(scale),
        interpret=jax.default_backend() != "tpu")


def attend_rows(q, pool_c, page_table, positions, *, latent_dim: int,
                scale: float, call_gate: str | None = None):
    """Absorbed latent attention of rows that each walk their own
    page-table row over everything up to their position. On a TPU the
    paged kernel, where the call can have one (`call_gate`: why it cannot,
    as the op's `_call_gate` says for a multi-device mesh) and
    `paged_latent_gate` passes; a geometry it refuses takes the XLA form
    and says so. Off a TPU the XLA form, as the serving path does for the
    other paged kernels."""
    if jax.default_backend() == "tpu":
        gate = call_gate or paged_latent_gate(
            page_table.shape[1], pool_c.shape[1], pool_c.shape[2],
            latent_dim, q.shape[1], pool_c.dtype.itemsize)
        if gate is None:
            return paged_latent_decode(q, pool_c, page_table, positions,
                                       latent_dim=latent_dim, scale=scale)
        warn_reference("paged_latent_decode", (q.shape, pool_c.shape), gate)
    return paged_latent_decode_reference(
        q, pool_c, page_table, positions, latent_dim=latent_dim, scale=scale)
