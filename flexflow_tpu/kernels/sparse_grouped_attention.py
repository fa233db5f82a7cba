"""Grouped-KV softmax attention over the rows a learned selection picked
(kernels/sparse_selection.py), read from a paged pool whose row is one
token's keys and values side by side, [k ; v] of 2 x kv_heads x head_dim:
a selected token is then ONE gathered row (XLA's row gather moves a row in
14-17 ns whatever its width: PERF.md section 6, PR 44), not one of keys
and one of values.

A slot's row gathers its selected rows of the pool (`attend_selected`); a
chunk's rows run dense over their shared context under the selection as a
mask (`attend_chunk`), as the latent pair of
kernels/sparse_latent_attention.py does. Query head i reads KV head
i // (heads // kv_heads). jax.numpy and `lax` only: the one Pallas kernel
of the selection is the decoding rows' indexer (sparse_selection.
paged_index_scores); the row gather here is XLA's (sparse_selection.
gather_selected; a kernel over the gathered rows gave 4 % of a decode step
and was left out: PERF.md section 6, PR 44) (tests/test_keye_vl2.py
holds these functions to the float32 reference and, where the selection is
everything, to the grouped paged kernels).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .sparse_selection import (
    NEG, _chunk_blocks, chunk_mask_blocks, gather_selected,
)


def _split(rows, kv_heads: int):
    """(k, v), each (.., kv_heads, head_dim), of pool rows (.., 2 x
    kv_heads x head_dim)."""
    half = rows.shape[-1] // 2
    shape = rows.shape[:-1] + (kv_heads, half // kv_heads)
    return rows[..., :half].reshape(shape), rows[..., half:].reshape(shape)


def attend_selected(q, pool_kv, page_table, sel, valid, *, kv_heads: int,
                    scale: float):
    """softmax(scale q . k) v of rows over their selected positions: q
    (rows, heads, head_dim) against the pool's rows [k ; v], page_table
    (rows, W), sel and valid (rows, K) -> (rows, heads, head_dim) in q's
    dtype. The rows are gathered from the pool as it lies, by (block,
    offset) (`gather_selected`)."""
    rows, heads, hd = q.shape
    picked = gather_selected(pool_kv, page_table, sel, valid)
    k, v = _split(picked.astype(q.dtype), kv_heads)
    qg = q.reshape(rows, kv_heads, heads // kv_heads, hd)
    scores = jnp.einsum("rgqd,rkgd->rgqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(valid[:, None, None, :], scores, NEG)
    probs = jax.nn.softmax(scores, axis=-1)
    # a dead row has no valid position: its softmax is uniform over
    # zeros' rows, finite, and nobody reads it
    out = jnp.einsum("rgqk,rkgd->rgqd", probs.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(rows, heads, hd).astype(q.dtype)


def attend_chunk(q, pool_kv, table, mask, positions, *, kv_heads: int,
                 scale: float):
    """Attention of the rows of one prefill chunk over the positions
    `mask` (rows, S) selects, all under the page-table row `table` (W,):
    the context's rows are read once, in blocks of pages up to the
    chunk's last live position, every row scores every key of a block and
    the mask picks (an online softmax over the blocks)."""
    rows, heads, hd = q.shape
    group = heads // kv_heads
    qg = q.reshape(rows, kv_heads, group, hd)
    table, p, span, blocks = _chunk_blocks(table, pool_kv.shape[1], positions)
    mask = chunk_mask_blocks(mask, table, pool_kv.shape[1])

    def body(i, carry):
        top, total, acc = carry
        pages = jax.lax.dynamic_slice(table, (i * p,), (p,))
        k, v = _split(pool_kv[pages].reshape(span, -1).astype(q.dtype),
                      kv_heads)
        picked = jax.lax.dynamic_slice(mask, (0, i * span), (rows, span))
        picked = picked[:, None, None, :]
        scores = jnp.einsum("rgqd,sgd->rgqs", qg, k,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(picked, scores, NEG)
        new_top = jnp.maximum(top, jnp.max(scores, axis=-1))
        probs = jnp.where(picked, jnp.exp(scores - new_top[..., None]), 0.0)
        keep = jnp.exp(top - new_top)
        acc = acc * keep[..., None] + jnp.einsum(
            "rgqs,sgd->rgqd", probs.astype(q.dtype), v,
            preferred_element_type=jnp.float32)
        return new_top, total * keep + jnp.sum(probs, axis=-1), acc

    top, total, acc = jax.lax.fori_loop(
        0, blocks, body,
        (jnp.full((rows, kv_heads, group), NEG, jnp.float32),
         jnp.zeros((rows, kv_heads, group), jnp.float32),
         jnp.zeros((rows, kv_heads, group, hd), jnp.float32)))
    # a dead row selected nothing: zeros, and nobody reads it
    out = acc / jnp.maximum(total, 1e-30)[..., None]
    return out.reshape(rows, heads, hd).astype(q.dtype)
