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

A chunk's rows take no top-k and no gather: their k-th largest score is
found by bisection and the selection is a mask (`selection_mask`), under
which the attention runs dense over the shared context.

All of it is jax.numpy and `lax` (XLA's gather, matmul, TopK): there is no
Pallas kernel here yet. The contract a kernel would have to keep is these
functions' (tests/test_latent_attention.py and tests/test_keye_vl2.py hold
them to the float32 references).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

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


def index_scores_rows(qi, wt, pool_i, page_table, positions):
    """Index scores (rows, S) of rows that each walk their own page-table
    row (rows, W), S = W x block: NEG past a row's position and for a dead
    row (position < 0)."""
    rows, W = page_table.shape
    bs, d = pool_i.shape[1], pool_i.shape[2]
    keys = pool_i[page_table].reshape(rows, W * bs, d)
    index = _weighted_relu(qi, wt, keys.astype(qi.dtype))
    seen = jnp.arange(W * bs)[None, :] <= positions[:, None]
    return jnp.where(seen, index, NEG)


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


def select_topk(index, k: int):
    """(positions (rows, K) int32, valid (rows, K)) of each row's K = min(k,
    S) largest index scores, exact; valid is False where a row has fewer
    candidates than K (its scores there are NEG)."""
    S = index.shape[1]
    if S <= k:
        sel = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), index.shape)
        return sel, index > NEG / 2
    vals, sel = jax.lax.top_k(index, k)
    return sel.astype(jnp.int32), vals > NEG / 2


def selection_mask(index, k: int):
    """(rows, S) bool: exactly each row's min(k, candidates) largest index
    scores, ties to the lower position as `lax.top_k` breaks them, with
    no sort: the k-th largest value by bisection on the scores' bits (33
    counting passes), then the ties at it by their rank."""
    rows, S = index.shape
    seen = index > NEG / 2
    if S <= k:
        return seen
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
    return seen & (above | (tied & (jnp.cumsum(tied, axis=-1) <= room)))


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
