"""Attention in the latent space over the rows a learned selection picked
(DeepSeek-V3.2's decode path), gathered from the paged latent pool by row.
The selection itself (the indexer's scores, the top-k, a chunk's mask) is
kernels/sparse_selection.py, shared with grouped-KV attention
(kernels/sparse_grouped_attention.py), and is reachable from here under
its old names.

A slot's row gathers its selected rows of `pool_c`; a chunk's rows run
dense over their shared context under the selection as a mask
(`attend_chunk`). jax.numpy and `lax` only: the one Pallas kernel of the
selection here is the decoding rows' indexer (sparse_selection.
paged_index_scores); the row gather is XLA's (sparse_selection.
gather_selected), and at 128 heads a group XLA's einsums over the gathered
rows are as fast as a kernel over them (PERF.md section 6, PR 44)
(tests/test_latent_attention.py holds these functions to the float32
reference).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .sparse_selection import (  # noqa: F401  (the ops call them from here)
    KEY_BLOCK_ROWS, NEG, _chunk_blocks, chunk_mask_blocks, gather_selected,
    index_scores_chunk, index_scores_rows, select_topk, selection_mask,
)


def attend_selected(q, pool_c, page_table, sel, valid, *, latent_dim: int,
                    scale: float):
    """Absorbed latent attention of rows over their selected positions:
    q (rows, heads, latent + rope) against the pool's rows [cKV ; k^R],
    page_table (rows, W), sel and valid (rows, K). Returns sum_s p_s
    cKV_s, (rows, heads, latent), in q's dtype. The rows are gathered
    from the pool as it lies, by (block, offset) (`gather_selected`)."""
    picked = gather_selected(pool_c, page_table, sel, valid).astype(q.dtype)
    scores = jnp.einsum("rhc,rkc->rhk", q, picked,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(valid[:, None, :], scores, NEG)
    probs = jax.nn.softmax(scores, axis=-1)
    # a dead row has no valid position: its softmax is uniform over
    # zeros' rows, finite, and nobody reads it
    return jnp.einsum("rhk,rkc->rhc", probs.astype(q.dtype),
                      picked[..., :latent_dim],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def attend_chunk(q, pool_c, table, mask, positions, *, latent_dim: int,
                 scale: float):
    """Absorbed latent attention of the rows of one prefill chunk over
    the positions `mask` (rows, S) selects, all under the page-table row
    `table` (W,): the context's latent rows are read once, in blocks of
    pages up to the chunk's last live position, every row scores every
    key of a block and the mask picks (an online softmax over the
    blocks). Dense in the context where `attend_selected` is sparse: 256
    rows that share their keys cost less so than 256 x 2,048 gathered
    rows (PERF.md section 6, PR 31). `mask` None: no selection, a row
    attends every position up to its own, and a block's mask is made from
    `positions` inside the walk (no (rows, S) mask exists)."""
    rows, heads, _ = q.shape
    table, p, span, blocks = _chunk_blocks(table, pool_c.shape[1], positions)
    if mask is not None:
        mask = chunk_mask_blocks(mask, table, pool_c.shape[1])

    def body(i, carry):
        top, total, acc = carry
        pages = jax.lax.dynamic_slice(table, (i * p,), (p,))
        keys = pool_c[pages].reshape(span, -1).astype(q.dtype)
        if mask is None:  # a dead row's position is -1: it attends nothing
            picked = (i * span + jnp.arange(span))[None] <= positions[:, None]
        else:
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
