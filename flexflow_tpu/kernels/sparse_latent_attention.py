"""The three device steps of sparse latent attention over a paged cache
(DeepSeek-V3.2's decode path): the lightning indexer's scores over a row's
cached indexer keys, the exact top-k of them, and attention in the latent
space over the rows that were selected, gathered from the pool by row.

A row is one query: a decoding slot's token, or one token of a prefill
chunk. Decoding rows each walk their own page-table row. The rows of a
chunk share one: their keys are gathered once and scored in blocks of
pages, as far as the chunk reaches (a `fori_loop` whose trip count is the
chunk's last position), so a chunk costs its context once, not once a
row.

A chunk's rows take no top-k and no gather: their k-th largest score is found
by bisection, the selection is a mask, and attention runs dense over the
shared context under it (`selection_mask`, `attend_chunk`).

All of it is jax.numpy and `lax` (XLA's gather, matmul, TopK): there is no
Pallas kernel here yet. The contract a kernel would have to keep is these
functions' (tests/test_latent_attention.py holds them to the float32
reference).
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


def _pages_per_block(W: int, bs: int) -> int:
    best = 1
    for p in range(1, W + 1):
        if W % p == 0 and p * bs <= max(KEY_BLOCK_ROWS, bs):
            best = p
    return best


def _chunk_blocks(table, bs: int, positions):
    """(pages a block, rows a block, blocks to walk) for a chunk under
    the page-table row `table`: as far as its last live position, none
    where the whole chunk is dead."""
    p = _pages_per_block(table.shape[0], bs)
    last = jnp.max(positions)
    return p, p * bs, jnp.where(last < 0, 0, last // (p * bs) + 1)


def index_scores_chunk(qi, wt, pool_i, table, positions):
    """Index scores (rows, S) of the rows of one prefill chunk, which
    share the page-table row `table` (W,): the keys are gathered once, in
    blocks of pages, up to the chunk's last live position."""
    rows = qi.shape[0]
    W, bs = table.shape[0], pool_i.shape[1]
    p, span, blocks = _chunk_blocks(table, bs, positions)

    def body(i, index):
        pages = jax.lax.dynamic_slice(table, (i * p,), (p,))
        keys = pool_i[pages].reshape(span, -1).astype(qi.dtype)
        return jax.lax.dynamic_update_slice(
            index, _weighted_relu(qi, wt, keys), (0, i * span))

    index = jax.lax.fori_loop(
        0, blocks, body, jnp.full((rows, W * bs), NEG, jnp.float32))
    seen = jnp.arange(W * bs)[None, :] <= positions[:, None]
    return jnp.where(seen, index, NEG)


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


def attend_selected(q, pool_c, page_table, sel, valid, *, latent_dim: int,
                    scale: float):
    """Absorbed latent attention of rows over their selected positions:
    q (rows, heads, latent + rope) against the pool's rows [cKV ; k^R],
    page_table (rows, W), sel and valid (rows, K). Returns sum_s p_s
    cKV_s, (rows, heads, latent), in q's dtype. The rows are gathered
    from the pool as it lies, by (block, offset): a flattened view would
    cost a copy of the pool."""
    bs = pool_c.shape[1]
    block = jnp.take_along_axis(page_table, sel // bs, axis=1)
    block = jnp.where(valid, block, 0)  # the scratch block: finite zeros
    picked = pool_c[block, sel % bs].astype(q.dtype)
    scores = jnp.einsum("rhc,rkc->rhk", q, picked,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(valid[:, None, :], scores, NEG)
    probs = jax.nn.softmax(scores, axis=-1)
    # a dead row has no valid position: its softmax is uniform over
    # zeros' rows, finite, and nobody reads it
    return jnp.einsum("rhk,rkc->rhc", probs.astype(q.dtype),
                      picked[..., :latent_dim],
                      preferred_element_type=jnp.float32).astype(q.dtype)


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


def attend_chunk(q, pool_c, table, mask, positions, *, latent_dim: int,
                 scale: float):
    """Absorbed latent attention of the rows of one prefill chunk over
    the positions `mask` (rows, S) selects, all under the page-table row
    `table` (W,): the context's latent rows are read once, in blocks of
    pages up to the chunk's last live position, every row scores every
    key of a block and the mask picks (an online softmax over the
    blocks). Dense in the context where `attend_selected` is sparse: 256
    rows that share their keys cost less so than 256 x 2,048 gathered
    rows (PERF.md section 6, PR 31)."""
    rows, heads, _ = q.shape
    p, span, blocks = _chunk_blocks(table, pool_c.shape[1], positions)

    def body(i, carry):
        top, total, acc = carry
        pages = jax.lax.dynamic_slice(table, (i * p,), (p,))
        keys = pool_c[pages].reshape(span, -1).astype(q.dtype)
        picked = jax.lax.dynamic_slice(mask, (0, i * span), (rows, span))
        scores = jnp.einsum("rhc,sc->rhs", q, keys,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(picked[:, None, :], scores, NEG)
        new_top = jnp.maximum(top, jnp.max(scores, axis=-1))
        probs = jnp.where(picked[:, None, :],
                          jnp.exp(scores - new_top[..., None]), 0.0)
        keep = jnp.exp(top - new_top)
        acc = acc * keep[..., None] + jnp.einsum(
            "rhs,sc->rhc", probs.astype(q.dtype), keys[:, :latent_dim],
            preferred_element_type=jnp.float32)
        return new_top, total * keep + jnp.sum(probs, axis=-1), acc

    top, total, acc = jax.lax.fori_loop(
        0, blocks, body,
        (jnp.full((rows, heads), NEG, jnp.float32),
         jnp.zeros((rows, heads), jnp.float32),
         jnp.zeros((rows, heads, latent_dim), jnp.float32)))
    # a dead row selected nothing: zeros, and nobody reads it
    return (acc / jnp.maximum(total, 1e-30)[..., None]).astype(q.dtype)
