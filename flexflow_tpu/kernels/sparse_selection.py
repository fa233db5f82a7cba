"""The learned sparse selection over a paged cache (DeepSeek-Sparse-
Attention's lightning indexer), whatever the attention that reads the
selected rows: the indexer's scores over a row's cached indexer keys, the
exact top-k of them, and, for a chunk's rows, the selection as a mask. The
two attentions over the selection are kernels/sparse_latent_attention.py
(latent rows [cKV ; k^R], DeepSeek-V3.2) and
kernels/sparse_grouped_attention.py (grouped keys and values).

A row is one query: a decoding slot's token, or one token of a prefill
chunk. Decoding rows each walk their own page-table row. The rows of a
chunk share one: their keys are gathered once and scored in blocks of
pages, as far as the chunk reaches (a `fori_loop` whose trip count is the
chunk's last position), so a chunk costs its context once, not once a
row.

The exact top-k is found with no sort, for both kinds of row: the k-th
largest score by bisection on the scores' bits and the ties at it by rank
give a mask (`_top_mask`). A chunk's rows take the mask (`selection_mask`)
and attend dense over the shared context under it, with no gather; a
decoding row's mask is compacted into its K positions, ascending
(`select_topk`), for the gather.

What is a kernel: the decoding rows' scores, `paged_index_scores`, a Pallas
kernel that walks a row's live pages by DMA and scores them in VMEM
(`index_scores_rows` takes it on one TPU where `paged_index_gate` passes,
and XLA's gather and einsum elsewhere; tests/test_paged_index_scores.py
holds the two to each other). What is still jax.numpy and `lax`: a chunk's
scores (`index_scores_chunk`: XLA's gather and matmul in a `fori_loop`),
the selection (a rolled loop of 33 counting passes, compares and two small
matmuls of 0s and 1s: the scores go out to HBM and come back for it, which
a bisection inside `paged_index_scores` would save), and the selected rows'
gather (`gather_selected`, for the two attention modules: Mosaic cannot
address one token's row of a tiled pool, and where it can a DMA a row costs
what XLA's gather costs, the comment above that function). The contract a
kernel has to keep is these functions' (tests/test_latent_attention.py and
tests/test_keye_vl2.py hold them to numpy and to the float32 references).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import warn_reference

NEG = -1e30
# rows of keys a chunk scores at a time: (chunk rows, indexer heads, this)
# float32 is the largest temporary, 168 MB at 256 x 64 x 2560
KEY_BLOCK_ROWS = 2560


def _weighted_relu(q, w, k):
    """sum_j w[r, j] relu(q[r, j] . k[.., s]) in float32: q (r, j, d), w
    (r, j), k (s, d) shared or (r, s, d) a row -> (r, s)."""
    spec = "rjd,sd->rjs" if k.ndim == 2 else "rjd,rsd->rjs"
    scores = jnp.einsum(spec, q, k, preferred_element_type=jnp.float32)
    return jnp.sum(w[:, :, None] * jax.nn.relu(scores), axis=1)


def index_scores_rows_reference(qi, wt, pool_i, page_table, positions):
    """`index_scores_rows` as XLA ops: every page of a table row gathered,
    live or not, then one einsum. The CPU serving path and the kernel's
    oracle."""
    rows, W = page_table.shape
    bs, d = pool_i.shape[1], pool_i.shape[2]
    keys = pool_i[page_table].reshape(rows, W * bs, d)
    index = _weighted_relu(qi, wt, keys.astype(qi.dtype))
    seen = jnp.arange(W * bs)[None, :] <= positions[:, None]
    return jnp.where(seen, index, NEG)


# ------------------------------------------------- the paged indexer
# One grid step a row, as the paged decode kernel walks (flash_attention.py,
# `_paged_decode_kernel`): the page table and the rows' lengths are scalar-
# prefetched, the pool stays in HBM, and the body copies the row's LIVE
# pages, whole pool rows of (block, lanes), a round of 4,096 keys at a time
# into one of two VMEM buffers, the next round in flight while this one is
# scored: (heads, d) @ (d, keys) on the MXU, ReLU, the heads' weights and
# their sum in float32, NEG behind the row's position, one store into the
# row's output. A page past the length is never a DMA; its scores are the
# NEG the output is filled with before the walk. The grid runs in order and
# a row's last round starts the next row's first, so only the call's first
# round waits for HBM with nothing to score. What this replaced gathered all
# W pages of every table row, wrote them out and read them back for the
# einsum: 3.26 ms for six layers of 16 rows at the cells' contexts against
# 0.72, 75 % of HBM speed for the bytes read (rounds of 2,048 keys 0.75, of
# 1,024 0.97; without the hand-over between rows 0.78: PERF.md section 6,
# PR 42).

_INDEX_ROUND_ROWS = 4096  # keys a DMA round: 1 MB of 128-lane bf16 rows
# two rounds' keys and the row's scores (a (1, S) float32 block lies one row
# to a tile of 8 sublanes at worst, and the pipeline keeps two) may take
# this much of the 16 MiB a Mosaic kernel gets by default; the rest is the
# compiler's: a round's (heads, keys) float32 scores
_INDEX_VMEM = 8 << 20


def _index_round_pages(width: int, block_size: int) -> int:
    return max(1, min(width, _INDEX_ROUND_ROWS // block_size))


def _paged_index_kernel(tbl_ref, len_ref, q_ref, w_ref, pool_hbm, o_ref,
                        k_buf, sem, base_ref):
    """One row's walk (the section comment above). q_ref (1, heads, d),
    w_ref (1, heads, 1) float32, o_ref (1, 1, S) float32; k_buf (2, keys a
    round, d), one DMA semaphore a buffer, and `base_ref`, the buffer the
    row's first round lies in, carried from grid step to grid step."""
    r = pl.program_id(0)
    rows, width = tbl_ref.shape
    bs = pool_hbm.shape[1]
    span = k_buf.shape[1]
    pages = span // bs
    length = len_ref[r]

    def live_pages(row):
        return pl.cdiv(len_ref[row], bs)

    def rounds(row):
        return pl.cdiv(live_pages(row), pages)

    n_rounds = rounds(r)

    def first_page(c):
        # a width the round does not divide: its last round ends at the
        # table's end and scores a few pages again, rather than run past it
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

    base = base_ref[0]
    nxt = jnp.minimum(r + 1, rows - 1)
    has_next = (r + 1 < rows) & (rounds(nxt) > 0)

    @pl.when((r == 0) & (n_rounds > 0))
    def _first():
        copies(r, 0, base, "start")

    o_ref[...] = jnp.full(o_ref.shape, NEG, o_ref.dtype)
    q = q_ref[0]   # (heads, d)
    w = w_ref[0]   # (heads, 1) float32

    @pl.loop(0, n_rounds)
    def _round(c):
        buf = (base + c) % 2

        @pl.when(c + 1 < n_rounds)
        def _next():
            copies(r, c + 1, 1 - buf, "start")

        @pl.when((c + 1 == n_rounds) & has_next)
        def _next_row():
            copies(nxt, 0, 1 - buf, "start")

        copies(r, c, buf, "wait")
        # rows of a page this round did not copy hold what the buffer held
        # (anything): a key's score is its own column, and theirs are masked
        scores = jax.lax.dot_general(
            q, k_buf[buf].astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (heads, span)
        index = jnp.sum(jax.nn.relu(scores) * w, axis=0, keepdims=True)
        first = pl.multiple_of(first_page(c) * bs, bs)
        key_pos = jax.lax.broadcasted_iota(jnp.int32, index.shape, 1) + first
        o_ref[0, :, pl.ds(first, span)] = jnp.where(key_pos < length, index,
                                                    NEG)

    @pl.when((n_rounds == 0) & has_next)
    def _dead_row():  # no last round to start the next row's beside
        copies(nxt, 0, base, "start")

    base_ref[0] = (base + n_rounds) % 2


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_index_call(table, lengths, qi, wt, pool_i, *, interpret: bool):
    """The kernel launch (shapes already gated), jitted for the memory
    space constraint as `flash_attention._paged_decode_call` is."""
    rows, heads, d = qi.shape
    W = table.shape[1]
    bs = pool_i.shape[1]
    span = _index_round_pages(W, bs) * bs
    if not interpret:
        # else XLA may park the pool in VMEM (PERF.md section 6, PR 26)
        pool_i = pltpu.with_memory_space_constraint(pool_i, pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rows,),
        in_specs=[
            pl.BlockSpec((1, heads, d), lambda r, tbl, ln: (r, 0, 0)),
            pl.BlockSpec((1, heads, 1), lambda r, tbl, ln: (r, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=pl.BlockSpec((1, 1, W * bs), lambda r, tbl, ln: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, span, pool_i.shape[2]), pool_i.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        _paged_index_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, 1, W * bs), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_index_scores",
    )(table, lengths, qi, wt.astype(jnp.float32)[:, :, None],
      pool_i).reshape(rows, W * bs)


def paged_index_gate(width: int, block_size: int, lanes: int,
                     itemsize: int) -> str | None:
    """Why the paged indexer kernel cannot take a pool of this geometry on
    a TPU, or None where it can: a block must be whole (sublane, lane)
    tiles in the round's buffer and its scores whole lane tiles of the
    row's output, the pool's row whole 128-lane tiles, and two rounds of
    keys beside the row's scores must sit in VMEM. Nothing here depends on
    how many rows a call has."""
    if block_size % 128 != 0:
        return (f"block_size {block_size} % 128 != 0: a page's scores are "
                f"no whole lane tiles")
    if lanes % 128 != 0:
        return f"indexer key rows of {lanes} lanes: no whole 128-lane tiles"
    span = _index_round_pages(width, block_size) * block_size
    need = 2 * span * lanes * itemsize + 2 * 8 * width * block_size * 4
    if need > _INDEX_VMEM:
        return (f"two rounds of {span} keys x {lanes} lanes and a row's "
                f"{width * block_size} scores take {need} bytes of VMEM > "
                f"{_INDEX_VMEM}")
    return None


def paged_index_scores(qi, wt, pool_i, page_table, positions):
    """`index_scores_rows` by the Pallas kernel, whatever the backend
    (interpret mode off a TPU: the kernel's own tests)."""
    W, bs = page_table.shape[1], pool_i.shape[1]
    lengths = jnp.clip(positions.astype(jnp.int32) + 1, 0, W * bs)
    return _paged_index_call(
        page_table.astype(jnp.int32), lengths, qi, wt, pool_i,
        interpret=jax.default_backend() != "tpu")


def index_scores_rows(qi, wt, pool_i, page_table, positions,
                      call_gate: str | None = None):
    """Index scores (rows, S) of rows that each walk their own page-table
    row (rows, W), S = W x block: NEG past a row's position and for a dead
    row (position < 0). qi (rows, heads, d), wt (rows, heads), pool_i
    (blocks, block, d). On a TPU the paged kernel, where the call can have
    one (`call_gate`: why it cannot, as the op's `_call_gate` says for a
    multi-device mesh) and `paged_index_gate` passes; a geometry it refuses
    takes the XLA form and says so. Off a TPU the XLA form, as the serving
    path does for the paged decode kernel."""
    if jax.default_backend() == "tpu":
        gate = call_gate or paged_index_gate(
            page_table.shape[1], pool_i.shape[1], pool_i.shape[2],
            pool_i.dtype.itemsize)
        if gate is None:
            return paged_index_scores(qi, wt, pool_i, page_table, positions)
        warn_reference("paged_index_scores", (qi.shape, pool_i.shape), gate)
    return index_scores_rows_reference(qi, wt, pool_i, page_table, positions)


# ------------------------------------------ the selected rows' gather
# A decoding row's selected pool rows are fetched by XLA's row gather, 14-17
# ns a row whatever its width. Why no kernel fetches them, one DMA a pool
# row: the pool lies in HBM in (8, 128) tiles, a token's row of it is eight
# strided pieces that share their 32-bit words with the neighbouring row,
# and Mosaic refuses a slice of a tiled memref that is no whole tile
# (tests/test_chip_compile.py keeps the refusal, bf16, float32 and the
# 32-bit view alike). Where Mosaic can address as many bytes (an (8, 128)
# tile of 8 tokens by 128 lanes of the pool as it lies: 2 KB, not a row; a
# row of a pool relaid token-major) a DMA costs 30-36 ns in a plain loop
# and 17.8 with the starts unrolled by 16 and one wait a round (PERF.md
# section 6, PR 44).


def gather_selected(pool, page_table, sel, valid):
    """(rows, K, lanes): the pool's rows at each row's selected positions,
    gathered from the pool as it lies, by (block, offset): a flattened
    view would cost a copy of the pool. An invalid entry reads the scratch
    block: finite zeros. A position's block is picked out of the row's
    page-table row by comparison, W selects an entry in one fusion: as a
    gather of scalars (`take_along_axis`) XLA looks the entries up one by
    one, 10 ns each, 0.33 ms a layer at 16 x 2,048 (PERF.md section 6,
    PR 44)."""
    bs, W = pool.shape[1], page_table.shape[1]
    page = jnp.where(valid, sel // bs, -1)[:, None, :]  # -1: no page
    hit = page == jnp.arange(W, dtype=page.dtype)[None, :, None]
    block = jnp.sum(jnp.where(hit, page_table[:, :, None], 0), axis=1)
    return pool[block, sel % bs]


def _chunk_blocks(table, bs: int, positions):
    """(the table, pages a block, rows a block, blocks to walk) for a
    chunk under the page-table row `table` (W,): blocks of as many pages
    as KEY_BLOCK_ROWS holds, walked as far as the chunk's last live
    position, none where the whole chunk is dead. A width the block does
    not divide (131 pages, a prime) is filled up with scratch pages, whose
    rows no position reaches, rather than walked a page at a time."""
    W = table.shape[0]
    p = max(1, min(W, max(KEY_BLOCK_ROWS, bs) // bs))
    table = jnp.pad(table, (0, -W % p))
    last = jnp.max(positions)
    return table, p, p * bs, jnp.where(last < 0, 0, last // (p * bs) + 1)


def index_scores_chunk(qi, wt, pool_i, table, positions):
    """Index scores (rows, S) of the rows of one prefill chunk, which
    share the page-table row `table` (W,): the keys are gathered once, in
    blocks of pages, up to the chunk's last live position."""
    rows = qi.shape[0]
    W, bs = table.shape[0], pool_i.shape[1]
    table, p, span, blocks = _chunk_blocks(table, bs, positions)

    def body(i, index):
        pages = jax.lax.dynamic_slice(table, (i * p,), (p,))
        keys = pool_i[pages].reshape(span, -1).astype(qi.dtype)
        return jax.lax.dynamic_update_slice(
            index, _weighted_relu(qi, wt, keys), (0, i * span))

    index = jax.lax.fori_loop(
        0, blocks, body,
        jnp.full((rows, table.shape[0] * bs), NEG, jnp.float32))[:, :W * bs]
    seen = jnp.arange(W * bs)[None, :] <= positions[:, None]
    return jnp.where(seen, index, NEG)


def chunk_mask_blocks(mask, table, bs: int):
    """`mask` (rows, W x bs) as wide as `_chunk_blocks`' table of `table`
    (False behind it), for a walk in whole blocks."""
    return jnp.pad(mask, ((0, 0), (0, table.shape[0] * bs - mask.shape[1])))


# ------------------------------------------------ the exact top-k
# One way to select, for decoding rows and chunk rows alike: the k-th
# largest score by bisection on the scores' bits, the ties at it by rank
# (`_top_mask`). A chunk's rows take the mask as it is; a decoding row's
# mask is compacted into its K positions (`_compact`). No sort: `lax.top_k`
# at k = 2,048 is a full sort of a row's 33 k scores, 0.55 ms a layer for
# 16 rows, a fifth of a decode step, and the attention over a selected set
# does not read the set's order. What XLA is slow at is kept out: a running
# count over 33 k columns (`jnp.cumsum`, a reduce-window: 0.108 ms a layer
# for 16 rows, three times the bisection) and a scatter or gather of
# scalars (10 ns each); both are small matmuls of 0s and 1s on the MXU,
# exact in bfloat16 with a float32 sum (PERF.md section 6, PR 47).

_WORD = 32  # positions a packed word of the mask
_GROUP = 8  # words a group: an output slot finds its group, then its word


def _running_count(flags):
    """(rows, S) int32: how many of a row's flags are set up to and with
    each position. Inside blocks of 128 a matmul with a triangle of ones,
    the blocks' totals by a short cumsum."""
    rows, S = flags.shape
    blocks = -(-S // 128)
    x = jnp.pad(flags, ((0, 0), (0, blocks * 128 - S)))
    inside = jnp.einsum(  # fflint: ok low_precision_accum (float32 sum)
        "rbl,lm->rbm", x.reshape(rows, blocks, 128).astype(jnp.bfloat16),
        jnp.triu(jnp.ones((128, 128), jnp.bfloat16)),
        preferred_element_type=jnp.float32).astype(jnp.int32)
    total = inside[:, :, -1]
    before = jnp.cumsum(total, axis=-1) - total
    return (inside + before[:, :, None]).reshape(rows, blocks * 128)[:, :S]


def _top_mask(index, k: int):
    """(rows, S) bool, S > k: exactly each row's min(k, candidates) largest
    index scores, ties to the lower position as `lax.top_k` breaks them:
    the k-th largest value by bisection on the scores' bits (33 counting
    passes, one rolled loop), then the ties at it by their rank."""
    rows = index.shape[0]
    bits = jax.lax.bitcast_convert_type(index, jnp.int32)
    keyed = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)  # order as floats

    def halve(_, bounds):
        lo, hi = bounds  # the k-th largest key lies in [lo, hi]
        mid = (lo | hi) - ((lo ^ hi) >> 1)  # ceil of the mean, no overflow
        enough = jnp.sum(keyed >= mid[:, None], axis=-1) >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid - 1)

    kth, _ = jax.lax.fori_loop(
        0, 33, halve, (jnp.full((rows,), -2**31, jnp.int32),
                       jnp.full((rows,), 2**31 - 1, jnp.int32)))
    above = keyed > kth[:, None]
    tied = keyed == kth[:, None]
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    first = tied & (_running_count(tied) <= room)
    return (index > NEG / 2) & (above | first)


def _compact(mask, K: int):
    """(positions (rows, K) int32, valid (rows, K)) of a mask (rows, S) with
    at most K set a row: the set positions ascending, `valid` over them, 0
    behind. The mask is packed 32 positions a word and the words counted
    in groups of `_GROUP`; output slot j lies in the one group whose running
    count spans j, and takes that group's words, first count and number by
    a one-hot matmul (groups x K compares and one small matmul over bytes,
    as `gather_selected` looks a block up by comparison); then the word and
    the bit inside it by their counts."""
    rows, S = mask.shape
    groups = -(-S // (_WORD * _GROUP))
    n = groups * _GROUP
    bits = jnp.pad(mask, ((0, 0), (0, _WORD * n - S))).reshape(rows, n, _WORD)
    words = jnp.sum(bits.astype(jnp.uint32)
                    << jnp.arange(_WORD, dtype=jnp.uint32), axis=-1)
    words = words.reshape(rows, groups, _GROUP)
    per = jnp.sum(jax.lax.population_count(words).astype(jnp.int32), axis=-1)
    end = jnp.cumsum(per, axis=-1)  # (rows, groups)
    start = end - per
    slot = jnp.arange(K, dtype=jnp.int32)
    hit = ((start[:, :, None] <= slot) & (slot < end[:, :, None]))
    table = jnp.concatenate(
        [words, start.astype(jnp.uint32)[:, :, None],
         jnp.broadcast_to(jnp.arange(groups, dtype=jnp.uint32)[None, :, None],
                          (rows, groups, 1))], axis=-1)
    planes = [table >> shift & 0xFF for shift in (0, 8, 16, 24)]
    got = jnp.einsum(  # fflint: ok low_precision_accum (float32 sum)
        "rgc,rgk->rck", jnp.concatenate(planes, -1).astype(jnp.bfloat16),
        hit.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
    got = got.astype(jnp.uint32).reshape(rows, 4, _GROUP + 2, K)
    got = got[:, 0] | got[:, 1] << 8 | got[:, 2] << 16 | got[:, 3] << 24
    word8 = got[:, :_GROUP]  # (rows, _GROUP, K): the slot's group
    rank = slot - got[:, _GROUP].astype(jnp.int32)  # among the group's
    group = got[:, _GROUP + 1].astype(jnp.int32)
    ones8 = jax.lax.population_count(word8).astype(jnp.int32)
    before = jnp.cumsum(ones8, axis=1) - ones8
    mine = (before <= rank[:, None]) & (rank[:, None] < before + ones8)
    which = jnp.sum(jnp.where(
        mine, jnp.arange(_GROUP, dtype=jnp.int32)[None, :, None], 0), axis=1)
    word = jnp.sum(jnp.where(mine, word8, 0), axis=1)
    rank = rank - jnp.sum(jnp.where(mine, before, 0), axis=1)
    bit = jnp.zeros_like(rank)
    for half in (16, 8, 4, 2, 1):  # the rank-th set bit of the word
        low = word & jnp.uint32((1 << half) - 1)
        under = jax.lax.population_count(low).astype(jnp.int32)
        up = rank >= under
        word = jnp.where(up, word >> half, low)
        rank = jnp.where(up, rank - under, rank)
        bit = bit + jnp.where(up, half, 0)
    valid = slot < end[:, -1:]
    sel = (group * _GROUP + which) * _WORD + bit
    return jnp.where(valid, sel, 0), valid


def select_topk(index, k: int):
    """(positions (rows, K) int32, valid (rows, K)) of each row's K = min(k,
    S) largest index scores, exact: `lax.top_k`'s set, ties to the lower
    position. `valid` is a prefix of a row, False where the row has fewer
    candidates than K (its scores there are NEG); the positions behind it
    are in range. The order inside the prefix is not the scores'."""
    S = index.shape[1]
    if S <= k:
        sel = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), index.shape)
        return sel, index > NEG / 2
    return _compact(_top_mask(index, k), k)


def selection_mask(index, k: int):
    """(rows, S) bool: exactly each row's min(k, candidates) largest index
    scores, ties to the lower position as `lax.top_k` breaks them, with
    no sort (`_top_mask`)."""
    if index.shape[1] <= k:
        return index > NEG / 2
    return _top_mask(index, k)


def causal_selection_mask(qi, wt, ki, topk: int):
    """The training-shaped form of the selection, dense: (batch, seq, seq)
    bool, the positions s <= t each t attends, the topk of largest index
    score, all of them while t < topk. qi (batch, seq, heads, d), wt
    (batch, seq, heads), ki (batch, seq, d)."""
    scores = jnp.einsum("btjd,bsd->btjs", qi, ki,
                        preferred_element_type=jnp.float32)
    index = jnp.sum(wt[..., None] * jax.nn.relu(scores), axis=2)
    s = index.shape[-1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    if s <= topk:
        return jnp.broadcast_to(causal, index.shape)
    # exactly topk of them, ties to the lower position as lax.top_k
    # breaks them (a ReLU makes exact zeros where indexer heads are few)
    sel = jax.lax.top_k(jnp.where(causal, index, NEG), topk)[1]
    picked = jnp.any(sel[..., None] == jnp.arange(s), axis=-2)
    return causal & picked
