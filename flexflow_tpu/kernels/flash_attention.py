"""Fused (flash) attention as a Pallas TPU kernel.

Replaces the reference's cuDNN `cudnnMultiHeadAttnForward` call
(src/ops/attention.cu:35) as the fast attention path. Design follows the
standard flash-attention blocking for TPU: grid over (batch*heads, q-blocks,
kv-blocks) with the kv axis innermost and sequential ("arbitrary"), a
(block_q, block_k) logits tile living in VMEM, and online-softmax running
max/denominator carried in VMEM scratch across kv steps. The MXU sees two
large matmuls per tile; HBM traffic is O(s*d) instead of the O(s^2)
materialized-probabilities tensor XLA would allocate at long sequence.

At short head_dim the kernel is VPU-bound (exp/mask/select passes over the
(block_q, block_k) tile dominate the two small MXU matmuls), so the tile
body is specialized three ways to do the minimum vector work:
  - dead tiles (strictly above the causal diagonal) are skipped entirely —
    with block < seq this halves the softmax work for causal attention;
  - interior tiles (strictly below the diagonal, no key tail) run with no
    iota/compare/select at all;
  - only diagonal / ragged-tail tiles pay for mask construction, and the
    masks that are statically all-true (seq divisible by block) are never
    built.
When the kv axis fits one block, the online-softmax scratch, init and
rescale passes are statically elided (one-pass softmax).

Backward is the FlashAttention-2 scheme as two Pallas kernels: the forward
saves per-row logsumexp; `delta = rowsum(dO*O)` is a cheap XLA elementwise
precompute; the dq kernel iterates kv-blocks per q-block and the dk/dv
kernel iterates q-blocks per kv-block, both recomputing the probability
tile from (q, k, lse) with the same three-way tile specialization.

On non-TPU backends (the 8-device CPU test mesh) the kernel runs in Pallas
interpret mode so tests exercise the same code path.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import warn_reference

NEG_INF = -1e30
# Minor dim of the (seq,) row-stat tensors (lse/delta): Mosaic wants
# 128-lane minor blocks for f32 (the in-tree jax flash kernel's
# MIN_BLOCK_SIZE); measured faster than an 8-lane layout on v5e despite the
# 16x larger residual, because every row-stat read in the bwd kernels is a
# lane-aligned block load.
LSE_LANES = 128


def _attn_reference(q, k, v, causal: bool, scale: float):
    """XLA-path attention (ops.attention.sdpa_xla): the small-shape fallback
    and the custom-VJP backward reference — one source of truth for attention
    numerics. Lazy import avoids a cycle (ops.attention lazily imports this
    module for impl="flash")."""
    from ..ops.attention import sdpa_xla

    return sdpa_xla(q, k, v, causal=causal, scale=scale)


def _shape_gate(s_q: int, s_k: int, d: int, causal: bool) -> str | None:
    """The training kernels' shape gate, by name (None = the kernel
    tiles). Tiny/ragged shapes go to the XLA path (still fused by XLA).
    Causal with s_q > s_k also routes there: rows with zero live keys
    (q_pos + offset < 0) would read m = -inf and p = exp(0) = 1 in the
    multi-kv online softmax — averaging V over live tiles only and
    emitting a bogus lse — instead of sdpa_xla's uniform-over-all-keys
    convention for that degenerate shape."""
    if s_q < 128 or s_k < 128:
        return f"seq {min(s_q, s_k)} < 128"
    if d % 8 != 0:
        return f"head_dim {d} % 8 != 0"
    if causal and s_q > s_k:
        return f"causal with s_q {s_q} > s_k {s_k}"
    return None


def _tile_classes(i, j, *, causal, block_q, block_k, causal_offset,
                  even_k, nj):
    """(live, needs_mask) predicates for tile (q-block i, kv-block j).

    A tile is live unless it lies strictly above the causal diagonal. It
    needs a mask if it straddles the diagonal or covers a ragged key tail;
    interior tiles run the unmasked fast path. Predicates are traced scalars
    (grid indices are dynamic) but the *structure* — whether a mask could
    ever be needed — is static Python, so fully-regular shapes compile no
    mask code at all."""
    if causal:
        live = j * block_k <= i * block_q + block_q - 1 + causal_offset
        # interior ⇔ the tile's top-right element (min q row, max k col) is
        # still on/below the diagonal
        interior = i * block_q + causal_offset >= j * block_k + block_k - 1
        needs_mask = jnp.logical_not(interior)
    else:
        live = True
        needs_mask = False
    if not even_k:
        tail = j == nj - 1
        needs_mask = jnp.logical_or(needs_mask, tail) if causal else tail
    return live, needs_mask


def _tile_mask(i, j, *, causal, block_q, block_k, seq_k, causal_offset,
               even_k):
    """Boolean (block_q, block_k) mask for a diagonal/tail tile. Only the
    statically-possible components are built."""
    mask = None
    if not even_k:
        k_pos = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        ) + j * block_k
        mask = k_pos < seq_k
    if causal:
        q_pos = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        ) + i * block_q
        k_pos = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        ) + j * block_k
        tri = q_pos + causal_offset >= k_pos
        mask = tri if mask is None else jnp.logical_and(mask, tri)
    return mask


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, *refs,
    scale: float, causal: bool, block_q: int, block_k: int, seq_k: int,
    causal_offset: int, save_lse: bool, nj: int,
    i_dim: int = 1, j_dim: int = 2,
):
    even_k = seq_k % block_k == 0
    single_kv = nj == 1
    if save_lse:
        lse_ref = refs[0]
        refs = refs[1:]
    else:
        lse_ref = None
    if not single_kv:
        m_ref, l_ref, acc_ref = refs
    i = pl.program_id(i_dim)
    j = pl.program_id(j_dim)

    def step(masked: bool):
        q = q_ref[0]  # (block_q, d)
        k = k_ref[0]  # (block_k, d)
        v = v_ref[0]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (block_q, block_k)
        if masked:
            mask = _tile_mask(
                i, j, causal=causal, block_q=block_q, block_k=block_k,
                seq_k=seq_k, causal_offset=causal_offset, even_k=even_k,
            )
            logits = jnp.where(mask, logits, NEG_INF)
            # Masked logits underflow to p == 0 exactly, so no second
            # probability mask is needed. A row with zero live keys (only
            # possible when causal and s_q > s_k) gets uniform p — the same
            # value sdpa_xla's softmax-of-constant-row produces, so the two
            # impls agree on that degenerate case.
            if not even_k:
                # zero padded V rows: OOB block rows hold garbage (NaN in
                # interpret mode) and 0·NaN would poison the contraction.
                v_valid = jax.lax.broadcasted_iota(
                    jnp.int32, v.shape, 0
                ) + j * block_k < seq_k
                v = jnp.where(v_valid, v, 0.0)

        if single_kv:
            # one-pass softmax: no scratch, no init/rescale passes
            m = logits.max(axis=-1)
            p = jnp.exp(logits - m[:, None])
            l = p.sum(axis=-1)
            acc = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)
            if save_lse:
                lse = m + jnp.log(l)
                lse_ref[0] = jnp.broadcast_to(lse[:, None], lse_ref[0].shape)
        else:
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[:, None])
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
            acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[...] = m_new

    if single_kv:
        # masked-ness is static: exactly one body is compiled
        masked = causal or not even_k
        step(masked)
        return

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live, needs_mask = _tile_classes(
        i, j, causal=causal, block_q=block_q, block_k=block_k,
        causal_offset=causal_offset,
        even_k=seq_k % block_k == 0, nj=nj,
    )
    if causal or seq_k % block_k != 0:
        live_masked = jnp.logical_and(live, needs_mask)
        live_clear = jnp.logical_and(live, jnp.logical_not(needs_mask))
        pl.when(live_masked)(lambda: step(True))
        pl.when(live_clear)(lambda: step(False))
    else:
        step(False)

    @pl.when(j == nj - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[...][:, None]).astype(o_ref.dtype)
        if save_lse:
            # row stats carry a minor dim of LSE_LANES so the block is
            # tile-legal on TPU (same trick as jax's in-tree flash kernel)
            lse = m_ref[...] + jnp.log(l_ref[...])
            lse_ref[0] = jnp.broadcast_to(lse[:, None], lse_ref[0].shape)


def _flash_fwd(q, k, v, causal: bool, scale: float,
               block_q: int, block_k: int, save_lse: bool = True):
    """save_lse=False (the primal / inference path) skips computing and
    writing the logsumexp residual entirely."""
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    bq = min(block_q, s_q)
    bk = min(block_k, s_k)
    qf = q.reshape(b * h, s_q, d)
    kf = k.reshape(b * h, s_k, d)
    vf = v.reshape(b * h, s_k, d)
    nj = pl.cdiv(s_k, bk)
    grid = (b * h, pl.cdiv(s_q, bq), nj)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        seq_k=s_k, causal_offset=s_k - s_q, save_lse=save_lse, nj=nj,
    )
    out_specs = [pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype)]
    if save_lse:
        out_specs.append(
            pl.BlockSpec((1, bq, LSE_LANES), lambda bh, i, j: (bh, i, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((b * h, s_q, LSE_LANES), jnp.float32))
    scratch_shapes = []
    if nj > 1:
        scratch_shapes = [
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ]
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=jax.default_backend() != "tpu",
        name="flash_attention_fwd",
    )(qf, kf, vf)
    if save_lse:
        out, lse = res
    else:
        (out,), lse = res, None
    return out.reshape(b, h, s_q, d), lse


def _bwd_tile_math(
    q, k, v, do, lse, delta, i, j, masked,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    seq_q: int, seq_k: int, causal_offset: int, mask_q_rows: bool,
):
    """Shared backward tile recompute on plain arrays: rebuild the
    probability tile p from (q, k, lse) and form ds = p*(dp - delta)*scale.
    Shared between the per-head ref-loading wrapper (`_bwd_tile`) and the
    grouped narrow-head kernels, which load lane sub-slices per head.

    Padded-row handling is static: q-row zeroing only exists when seq_q is
    ragged against block_q (garbage rows are NaN in interpret mode and
    0*NaN would poison contractions), kv-row zeroing only when seq_k is
    ragged against block_k. mask_q_rows additionally joins q-row validity
    into the probability mask: padded q rows have p == exp(0-0) == 1 and
    must not leak into reductions over the q axis (dk/dv); reductions over
    the kv axis (dq) don't need it because their padded output rows are
    discarded on write."""
    even_q = seq_q % block_q == 0
    even_k = seq_k % block_k == 0
    q_valid = None
    if not even_q:
        q_valid = jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0
        ) + i * block_q < seq_q
        q = jnp.where(q_valid, q, 0.0)
        do = jnp.where(q_valid, do, 0.0)
        lse = jnp.where(q_valid[:, 0], lse, 0.0)
        delta = jnp.where(q_valid[:, 0], delta, 0.0)
    if not even_k:
        kv_valid = jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0
        ) + j * block_k < seq_k
        k = jnp.where(kv_valid, k, 0.0)
        v = jnp.where(kv_valid, v, 0.0)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    mask = None
    if masked:
        mask = _tile_mask(
            i, j, causal=causal, block_q=block_q, block_k=block_k,
            seq_k=seq_k, causal_offset=causal_offset, even_k=even_k,
        )
    if mask_q_rows and q_valid is not None:
        mask = q_valid if mask is None else jnp.logical_and(mask, q_valid)
    p = jnp.exp(s - lse[:, None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta[:, None]) * scale
    return q, k, v, do, p, ds


def _bwd_tile(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, i, j, masked,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    seq_q: int, seq_k: int, causal_offset: int, mask_q_rows: bool,
):
    """Ref-loading wrapper around `_bwd_tile_math` for the per-head
    kernels (one head per block; leading singleton block dim)."""
    return _bwd_tile_math(
        q_ref[0], k_ref[0], v_ref[0], do_ref[0],
        lse_ref[0][:, 0], delta_ref[0][:, 0], i, j, masked,
        scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        seq_q=seq_q, seq_k=seq_k, causal_offset=causal_offset,
        mask_q_rows=mask_q_rows)


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    seq_q: int, seq_k: int, causal_offset: int, nj: int,
    i_dim: int = 1, j_dim: int = 2,
):
    i = pl.program_id(i_dim)
    j = pl.program_id(j_dim)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def step(masked: bool):
        q, k, _, do, p, ds = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, i, j, masked,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            seq_q=seq_q, seq_k=seq_k, causal_offset=causal_offset,
            mask_q_rows=False,  # padded dq rows are discarded on write
        )
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    live, needs_mask = _tile_classes(
        i, j, causal=causal, block_q=block_q, block_k=block_k,
        causal_offset=causal_offset, even_k=seq_k % block_k == 0, nj=nj,
    )
    if causal or seq_k % block_k != 0:
        pl.when(jnp.logical_and(live, needs_mask))(lambda: step(True))
        pl.when(jnp.logical_and(live, jnp.logical_not(needs_mask)))(
            lambda: step(False))
    else:
        step(False)

    @pl.when(j == nj - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    seq_q: int, seq_k: int, causal_offset: int, ni: int, nj: int,
    i_dim: int = 2, j_dim: int = 1,
):
    j = pl.program_id(j_dim)  # kv block
    i = pl.program_id(i_dim)  # q block (innermost, sequential)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(masked: bool):
        q, _, _, do, p, ds = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, i, j, masked,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
            seq_q=seq_q, seq_k=seq_k, causal_offset=causal_offset,
            mask_q_rows=True,  # padded q rows would leak p==1 into dk/dv
        )
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    live, needs_mask = _tile_classes(
        i, j, causal=causal, block_q=block_q, block_k=block_k,
        causal_offset=causal_offset, even_k=seq_k % block_k == 0, nj=nj,
    )
    # (a ragged q tail needs no masked-path forcing here: _bwd_tile joins
    # q-row validity into the probability mask independently of `masked`)
    if causal or seq_k % block_k != 0:
        pl.when(jnp.logical_and(live, needs_mask))(lambda: step(True))
        pl.when(jnp.logical_and(live, jnp.logical_not(needs_mask)))(
            lambda: step(False))
    else:
        step(False)

    @pl.when(i == ni - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_single_tile_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dk_ref, dv_ref,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    seq_q: int, seq_k: int, causal_offset: int,
):
    """Whole-sequence backward in ONE kernel (seq fits a single tile): the
    probability tile and ds are computed once and reused for dq, dk, AND
    dv — the split dq/dkv FA2 kernels each recompute them, costing a
    second exp pass over the logits tile. At short-to-medium sequence this
    is the dominant backward cost (the kernels are VPU-bound, like the
    forward)."""
    zero = jnp.zeros((), jnp.int32)
    q, k, v, do, p, ds = _bwd_tile(
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, zero, zero,
        True,  # single tile is always the diagonal tile under causal
        scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        seq_q=seq_q, seq_k=seq_k, causal_offset=causal_offset,
        # invariant of this kernel: the caller fixes block == seq, so
        # there are never padded q rows to mask
        mask_q_rows=False,
    )
    dq_ref[0] = jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dq_ref.dtype)
    dk_ref[0] = jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dk_ref.dtype)
    dv_ref[0] = jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dv_ref.dtype)


def _flash_bwd_single_tile(qf, kf, vf, gf, lse, delta, causal, scale,
                           s_q, s_k, d, bh):
    spec = pl.BlockSpec((1, s_q, d), lambda i: (i, 0, 0))
    kspec = pl.BlockSpec((1, s_k, d), lambda i: (i, 0, 0))
    rowspec = pl.BlockSpec((1, s_q, LSE_LANES), lambda i: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(
            _bwd_single_tile_kernel, scale=scale, causal=causal,
            block_q=s_q, block_k=s_k, seq_q=s_q, seq_k=s_k,
            causal_offset=s_k - s_q,
        ),
        grid=(bh,),
        in_specs=[spec, kspec, kspec, spec, rowspec, rowspec],
        out_specs=[spec, kspec, kspec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, d), qf.dtype),
            jax.ShapeDtypeStruct((bh, s_k, d), kf.dtype),
            jax.ShapeDtypeStruct((bh, s_k, d), vf.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=jax.default_backend() != "tpu",
        name="flash_attention_bwd_fused",
    )(qf, kf, vf, gf, lse, delta)


def _flash_bwd(q, k, v, out, lse, g, causal, scale, block_q, block_k,
               delta_adj=None):
    """`delta_adj` (b, h, s_q), when given, is SUBTRACTED from delta before
    the kernels run: the lse cotangent of the with-lse forward. Derivation:
    ∂lse_i/∂s_ij = p_ij, so a g_lse cotangent adds p·g_lse to ds — i.e.
    ds = p·(dp − (delta − g_lse)), a pure delta shift. dv = pᵀ·do is
    unaffected, so the same dq/dkv kernels serve both VJPs."""
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    bq = min(block_q, s_q)
    bk = min(block_k, s_k)
    qf = q.reshape(b * h, s_q, d)
    kf = k.reshape(b * h, s_k, d)
    vf = v.reshape(b * h, s_k, d)
    gf = g.reshape(b * h, s_q, d)
    # delta_i = rowsum(dO_i * O_i) — cheap elementwise XLA precompute
    delta = jnp.sum(
        gf.astype(jnp.float32) * out.reshape(b * h, s_q, d).astype(jnp.float32),
        axis=-1,
    )
    if delta_adj is not None:
        delta = delta - delta_adj.reshape(b * h, s_q).astype(jnp.float32)
    delta = jnp.broadcast_to(delta[..., None], (b * h, s_q, LSE_LANES))
    interpret = jax.default_backend() != "tpu"
    ni = pl.cdiv(s_q, bq)
    nj = pl.cdiv(s_k, bk)
    if ni == 1 and nj == 1:
        dq, dk, dv = _flash_bwd_single_tile(
            qf, kf, vf, gf, lse, delta, causal, scale, s_q, s_k, d, b * h)
        return (
            dq.reshape(b, h, s_q, d),
            dk.reshape(b, h, s_k, d),
            dv.reshape(b, h, s_k, d),
        )
    common = dict(
        scale=scale, causal=causal, block_q=bq, block_k=bk,
        seq_q=s_q, seq_k=s_k, causal_offset=s_k - s_q,
    )
    qspec = pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0))
    kspec = pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0))
    rowspec = pl.BlockSpec((1, bq, LSE_LANES), lambda bh, i, j: (bh, i, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, nj=nj, **common),
        grid=(b * h, ni, nj),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(qf, kf, vf, gf, lse, delta)
    # kv-grid kernel: block index maps take (bh, kv_j, q_i)
    qspec2 = pl.BlockSpec((1, bq, d), lambda bh, j, i: (bh, i, 0))
    kspec2 = pl.BlockSpec((1, bk, d), lambda bh, j, i: (bh, j, 0))
    rowspec2 = pl.BlockSpec((1, bq, LSE_LANES), lambda bh, j, i: (bh, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, ni=ni, nj=nj, **common),
        grid=(b * h, nj, ni),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowspec2, rowspec2],
        out_specs=[kspec2, kspec2],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, s_k, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(qf, kf, vf, gf, lse, delta)
    return (
        dq.reshape(b, h, s_q, d),
        dk.reshape(b, h, s_k, d),
        dv.reshape(b, h, s_k, d),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, block_q, block_k):
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                        save_lse=False)
    return out


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, res, g):
    q, k, v, out, lse = res
    return _flash_bwd(q, k, v, out, lse, g, causal, scale, block_q, block_k)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ----------------------------------------------------- (out, lse) variant
# Ring attention combines per-block partial softmaxes across K/V rotations
# (parallel/ring_attention.py): each block contributes (out_blk, lse_blk)
# and the online merge is out = Σ out_blk·exp(lse_blk − lse) with
# lse = logaddexp over blocks. Both outputs carry gradients (the merge
# weights depend on lse), so this variant's VJP folds the lse cotangent
# into delta (see _flash_bwd) instead of inventing a second backward.


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_lse(q, k, v, causal, scale, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k)
    b, h, s_q, _ = q.shape
    return out, lse[:, :, 0].reshape(b, h, s_q)


def _flash_lse_vjp_fwd(q, k, v, causal, scale, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k)
    b, h, s_q, _ = q.shape
    return ((out, lse[:, :, 0].reshape(b, h, s_q)),
            (q, k, v, out, lse))


def _flash_lse_vjp_bwd(causal, scale, block_q, block_k, res, g):
    q, k, v, out, lse = res
    g_out, g_lse = g
    return _flash_bwd(q, k, v, out, lse, g_out, causal, scale,
                      block_q, block_k, delta_adj=g_lse)


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def _attn_reference_lse(q, k, v, causal: bool, scale: float):
    """XLA-path (out, lse) with sdpa_xla's exact masking convention — the
    small-shape fallback of flash_attention_with_lse. lse over masked
    (-1e30) logits matches the kernel's live-keys logsumexp to f32 eps."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v), lse


def flash_attention_with_lse(
    q, k, v, *, causal: bool = False, scale: float | None = None,
    block_q: int = 512, block_k: int = 512,
):
    """Fused attention returning (out, lse). q,k,v: (b, h, s, d); lse:
    (b, h, s_q) float32 row logsumexp of the scaled (masked) logits.
    Differentiable in BOTH outputs (the lse cotangent folds into delta in
    the shared FA2 backward). Same shape gates as flash_attention."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    gate = _shape_gate(q.shape[2], k.shape[2], q.shape[3], causal)
    if gate is not None:
        warn_reference("flash_attention_with_lse", (q.shape, k.shape), gate)
        return _attn_reference_lse(q, k, v, causal, scale)
    return _flash_lse(q, k, v, causal, scale, block_q, block_k)


# --------------------------------------------------------- packed layout
# (b, s, h·dh) activations end to end: the qkv projection's natural output
# layout. Heads are selected by BlockSpec lane-offset index maps — block
# index h on the last (h·dh)-wide dim — so NO head transpose/relayout ever
# touches HBM (PERF.md measured the (b,s,h,d)→(b,h,s,d) copies at ~0.8 ms
# per flagship step). The kernel bodies are shared with the bhsd path; only
# the grids ((b, h, qi, kj)) and index maps differ.
#
# NARROW HEADS (head_dim < 128): Mosaic requires a lane block be a multiple
# of 128 lanes (or the full array width), so a single head_dim-64 head
# cannot be its own block — the old gate routed those models through the
# transposed layout and paid the relayout. The grouped path below removes
# that: blocks take a GROUP of `hpb` consecutive heads per 128-lane stripe
# (hpb = 128/dh when dh | 128, else all heads — full array width, legal for
# any dh), the grid gains a head-GROUP dimension, and the kernel bodies
# loop statically over the group's heads via lane sub-slices — the same
# (b, s, h, d) block semantics as a 4-D BlockSpec with a head grid dim,
# expressed on the 3-D packed array so no reshape/relayout ever runs.


def _packed_heads_per_block(head_dim: int, num_heads: int) -> int:
    """Heads per lane block for the packed path. 1 = the classic one-head
    lane-offset blocks (head_dim % 128 == 0); >1 = the grouped narrow-head
    path. Always yields a Mosaic-legal lane width: hpb·dh is either a
    multiple of 128 or the full (h·dh) array width."""
    if head_dim % 128 == 0:
        return 1
    if 128 % head_dim == 0 and num_heads % (128 // head_dim) == 0:
        return 128 // head_dim
    return num_heads


def _flash_kernel_grouped(
    q_ref, k_ref, v_ref, o_ref, *refs,
    scale: float, causal: bool, block_q: int, block_k: int, seq_k: int,
    causal_offset: int, save_lse: bool, nj: int, hpb: int, head_dim: int,
):
    """Forward tile for a HEAD GROUP: same online-softmax math as
    _flash_kernel, looped statically over the hpb heads of the block's
    lane stripe. Row stats live per head ((hpb, bq) scratch); the
    accumulator shares the block's (bq, hpb·dh) lane layout."""
    even_k = seq_k % block_k == 0
    single_kv = nj == 1
    if save_lse:
        lse_ref = refs[0]
        refs = refs[1:]
    else:
        lse_ref = None
    if not single_kv:
        m_ref, l_ref, acc_ref = refs
    i = pl.program_id(2)
    j = pl.program_id(3)

    def step(masked: bool):
        mask = v_valid = None
        if masked:
            mask = _tile_mask(
                i, j, causal=causal, block_q=block_q, block_k=block_k,
                seq_k=seq_k, causal_offset=causal_offset, even_k=even_k,
            )
            if not even_k:
                v_valid = jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, head_dim), 0
                ) + j * block_k < seq_k
        for hh in range(hpb):
            sl = slice(hh * head_dim, (hh + 1) * head_dim)
            q = q_ref[0][:, sl]
            k = k_ref[0][:, sl]
            v = v_ref[0][:, sl]
            logits = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            if masked:
                logits = jnp.where(mask, logits, NEG_INF)
                if not even_k:
                    v = jnp.where(v_valid, v, 0.0)
            if single_kv:
                m = logits.max(axis=-1)
                p = jnp.exp(logits - m[:, None])
                l = p.sum(axis=-1)
                acc = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                o_ref[0, :, sl] = (acc / l[:, None]).astype(o_ref.dtype)
                if save_lse:
                    lse_ref[hh] = jnp.broadcast_to(
                        (m + jnp.log(l))[:, None], lse_ref.shape[1:])
            else:
                m_prev = m_ref[hh]
                m_new = jnp.maximum(m_prev, logits.max(axis=-1))
                p = jnp.exp(logits - m_new[:, None])
                alpha = jnp.exp(m_prev - m_new)
                l_ref[hh] = l_ref[hh] * alpha + p.sum(axis=-1)
                acc_ref[:, sl] = (acc_ref[:, sl] * alpha[:, None]
                                  + jax.lax.dot_general(
                                      p.astype(v.dtype), v,
                                      (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32))
                m_ref[hh] = m_new

    if single_kv:
        step(causal or not even_k)
        return

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live, needs_mask = _tile_classes(
        i, j, causal=causal, block_q=block_q, block_k=block_k,
        causal_offset=causal_offset, even_k=even_k, nj=nj,
    )
    if causal or not even_k:
        pl.when(jnp.logical_and(live, needs_mask))(lambda: step(True))
        pl.when(jnp.logical_and(live, jnp.logical_not(needs_mask)))(
            lambda: step(False))
    else:
        step(False)

    @pl.when(j == nj - 1)
    def _finish():
        for hh in range(hpb):
            sl = slice(hh * head_dim, (hh + 1) * head_dim)
            o_ref[0, :, sl] = (acc_ref[:, sl]
                               / l_ref[hh][:, None]).astype(o_ref.dtype)
            if save_lse:
                lse_ref[hh] = jnp.broadcast_to(
                    (m_ref[hh] + jnp.log(l_ref[hh]))[:, None],
                    lse_ref.shape[1:])


def _bwd_dq_kernel_grouped(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    seq_q: int, seq_k: int, causal_offset: int, nj: int,
    hpb: int, head_dim: int,
):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def step(masked: bool):
        for hh in range(hpb):
            sl = slice(hh * head_dim, (hh + 1) * head_dim)
            _, k, _, _, _, ds = _bwd_tile_math(
                q_ref[0][:, sl], k_ref[0][:, sl], v_ref[0][:, sl],
                do_ref[0][:, sl], lse_ref[hh][:, 0], delta_ref[hh][:, 0],
                i, j, masked,
                scale=scale, causal=causal, block_q=block_q,
                block_k=block_k, seq_q=seq_q, seq_k=seq_k,
                causal_offset=causal_offset,
                mask_q_rows=False,  # padded dq rows are discarded on write
            )
            dq_acc[:, sl] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    live, needs_mask = _tile_classes(
        i, j, causal=causal, block_q=block_q, block_k=block_k,
        causal_offset=causal_offset, even_k=seq_k % block_k == 0, nj=nj,
    )
    if causal or seq_k % block_k != 0:
        pl.when(jnp.logical_and(live, needs_mask))(lambda: step(True))
        pl.when(jnp.logical_and(live, jnp.logical_not(needs_mask)))(
            lambda: step(False))
    else:
        step(False)

    @pl.when(j == nj - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel_grouped(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, scale: float, causal: bool, block_q: int, block_k: int,
    seq_q: int, seq_k: int, causal_offset: int, ni: int, nj: int,
    hpb: int, head_dim: int,
):
    j = pl.program_id(2)  # kv block
    i = pl.program_id(3)  # q block (innermost, sequential)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(masked: bool):
        for hh in range(hpb):
            sl = slice(hh * head_dim, (hh + 1) * head_dim)
            q, _, _, do, p, ds = _bwd_tile_math(
                q_ref[0][:, sl], k_ref[0][:, sl], v_ref[0][:, sl],
                do_ref[0][:, sl], lse_ref[hh][:, 0], delta_ref[hh][:, 0],
                i, j, masked,
                scale=scale, causal=causal, block_q=block_q,
                block_k=block_k, seq_q=seq_q, seq_k=seq_k,
                causal_offset=causal_offset,
                mask_q_rows=True,  # padded q rows would leak p==1 into dk/dv
            )
            dv_acc[:, sl] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dk_acc[:, sl] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    live, needs_mask = _tile_classes(
        i, j, causal=causal, block_q=block_q, block_k=block_k,
        causal_offset=causal_offset, even_k=seq_k % block_k == 0, nj=nj,
    )
    if causal or seq_k % block_k != 0:
        pl.when(jnp.logical_and(live, needs_mask))(lambda: step(True))
        pl.when(jnp.logical_and(live, jnp.logical_not(needs_mask)))(
            lambda: step(False))
    else:
        step(False)

    @pl.when(i == ni - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_fwd_packed_grouped(q, k, v, num_heads, causal, scale,
                              block_q, block_k, hpb, save_lse=True):
    """Narrow-head forward: head-GROUP lane blocks (hpb heads per block,
    width hpb·d = 128-multiple or full array width) over the 3-D packed
    array, grid (b, head-groups, q-blocks, kv-blocks)."""
    b, s_q, e = q.shape
    s_k = k.shape[1]
    h = num_heads
    d = e // h
    ng = h // hpb
    bq = min(block_q, s_q)
    bk = min(block_k, s_k)
    nj = pl.cdiv(s_k, bk)
    grid = (b, ng, pl.cdiv(s_q, bq), nj)
    kernel = functools.partial(
        _flash_kernel_grouped, scale=scale, causal=causal, block_q=bq,
        block_k=bk, seq_k=s_k, causal_offset=s_k - s_q, save_lse=save_lse,
        nj=nj, hpb=hpb, head_dim=d,
    )
    w = hpb * d
    qspec = pl.BlockSpec((1, bq, w), lambda bi, gi, i, j: (bi, i, gi))
    kspec = pl.BlockSpec((1, bk, w), lambda bi, gi, i, j: (bi, j, gi))
    out_specs = [qspec]
    out_shape = [jax.ShapeDtypeStruct((b, s_q, e), q.dtype)]
    if save_lse:
        # per-head row stats in the (b·h, s, LANES) layout; the group's
        # hpb consecutive head rows form one block
        out_specs.append(pl.BlockSpec(
            (hpb, bq, LSE_LANES),
            lambda bi, gi, i, j: (bi * ng + gi, i, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((b * h, s_q, LSE_LANES), jnp.float32))
    scratch_shapes = []
    if nj > 1:
        scratch_shapes = [
            pltpu.VMEM((hpb, bq), jnp.float32),
            pltpu.VMEM((hpb, bq), jnp.float32),
            pltpu.VMEM((bq, w), jnp.float32),
        ]
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[qspec, kspec, kspec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=jax.default_backend() != "tpu",
        name="flash_attention_fwd_packed_grouped",
    )(q, k, v)
    if save_lse:
        return res[0], res[1]
    return res[0], None


def _flash_fwd_packed(q, k, v, num_heads, causal, scale,
                      block_q, block_k, save_lse=True):
    b, s_q, e = q.shape
    s_k = k.shape[1]
    h = num_heads
    d = e // h
    hpb = _packed_heads_per_block(d, h)
    if hpb > 1:
        return _flash_fwd_packed_grouped(q, k, v, num_heads, causal, scale,
                                         block_q, block_k, hpb, save_lse)
    bq = min(block_q, s_q)
    bk = min(block_k, s_k)
    nj = pl.cdiv(s_k, bk)
    grid = (b, h, pl.cdiv(s_q, bq), nj)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        seq_k=s_k, causal_offset=s_k - s_q, save_lse=save_lse, nj=nj,
        i_dim=2, j_dim=3,
    )
    qspec = pl.BlockSpec((1, bq, d), lambda bi, hi, i, j: (bi, i, hi))
    kspec = pl.BlockSpec((1, bk, d), lambda bi, hi, i, j: (bi, j, hi))
    out_specs = [qspec]
    out_shape = [jax.ShapeDtypeStruct((b, s_q, e), q.dtype)]
    if save_lse:
        # row stats stay in the (b·h, s, LANES) layout the shared kernel
        # bodies index; the flat block row is computed from (bi, hi)
        out_specs.append(pl.BlockSpec(
            (1, bq, LSE_LANES), lambda bi, hi, i, j: (bi * h + hi, i, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((b * h, s_q, LSE_LANES), jnp.float32))
    scratch_shapes = []
    if nj > 1:
        scratch_shapes = [
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ]
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[qspec, kspec, kspec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=jax.default_backend() != "tpu",
        name="flash_attention_fwd_packed",
    )(q, k, v)
    if save_lse:
        return res[0], res[1]
    return res[0], None


def _flash_bwd_packed_grouped(q, k, v, g, lse, delta, num_heads, causal,
                              scale, block_q, block_k, hpb):
    """Narrow-head dq + dkv kernels on head-group lane blocks (the
    single-tile fused specialization is per-head-only; grouped shapes
    route through the split FA2 pair even at one tile)."""
    b, s_q, e = q.shape
    s_k = k.shape[1]
    h = num_heads
    d = e // h
    ng = h // hpb
    w = hpb * d
    bq = min(block_q, s_q)
    bk = min(block_k, s_k)
    ni = pl.cdiv(s_q, bq)
    nj = pl.cdiv(s_k, bk)
    interpret = jax.default_backend() != "tpu"
    common = dict(
        scale=scale, causal=causal, block_q=bq, block_k=bk,
        seq_q=s_q, seq_k=s_k, causal_offset=s_k - s_q, hpb=hpb, head_dim=d,
    )
    qspec = pl.BlockSpec((1, bq, w), lambda bi, gi, i, j: (bi, i, gi))
    kspec = pl.BlockSpec((1, bk, w), lambda bi, gi, i, j: (bi, j, gi))
    rowspec = pl.BlockSpec((hpb, bq, LSE_LANES),
                           lambda bi, gi, i, j: (bi * ng + gi, i, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_grouped, nj=nj, **common),
        grid=(b, ng, ni, nj),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b, s_q, e), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_bwd_dq_packed_grouped",
    )(q, k, v, g, lse, delta)
    # kv-grid kernels: block index maps take (b, group, kv_j, q_i)
    qspec2 = pl.BlockSpec((1, bq, w), lambda bi, gi, j, i: (bi, i, gi))
    kspec2 = pl.BlockSpec((1, bk, w), lambda bi, gi, j, i: (bi, j, gi))
    rowspec2 = pl.BlockSpec((hpb, bq, LSE_LANES),
                            lambda bi, gi, j, i: (bi * ng + gi, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_grouped, ni=ni, nj=nj, **common),
        grid=(b, ng, nj, ni),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowspec2, rowspec2],
        out_specs=[kspec2, kspec2],
        out_shape=[
            jax.ShapeDtypeStruct((b, s_k, e), k.dtype),
            jax.ShapeDtypeStruct((b, s_k, e), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, w), jnp.float32),
            pltpu.VMEM((bk, w), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_bwd_dkv_packed_grouped",
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


def _flash_bwd_packed(q, k, v, out, lse, g, num_heads, causal, scale,
                      block_q, block_k):
    b, s_q, e = q.shape
    s_k = k.shape[1]
    h = num_heads
    d = e // h
    bq = min(block_q, s_q)
    bk = min(block_k, s_k)
    # delta = rowsum(dO·O) per head: reduce dh inside each head, then a
    # tiny (b, s, h) transpose — no (·, d)-sized relayout
    delta = jnp.sum(
        (g.astype(jnp.float32) * out.astype(jnp.float32))
        .reshape(b, s_q, h, d),
        axis=-1,
    ).transpose(0, 2, 1).reshape(b * h, s_q)
    delta = jnp.broadcast_to(delta[..., None], (b * h, s_q, LSE_LANES))
    hpb = _packed_heads_per_block(d, h)
    if hpb > 1:
        return _flash_bwd_packed_grouped(q, k, v, g, lse, delta, num_heads,
                                         causal, scale, block_q, block_k,
                                         hpb)
    interpret = jax.default_backend() != "tpu"
    ni = pl.cdiv(s_q, bq)
    nj = pl.cdiv(s_k, bk)
    common = dict(
        scale=scale, causal=causal, block_q=bq, block_k=bk,
        seq_q=s_q, seq_k=s_k, causal_offset=s_k - s_q,
    )
    if ni == 1 and nj == 1:
        spec = pl.BlockSpec((1, s_q, d), lambda bi, hi: (bi, 0, hi))
        kspec = pl.BlockSpec((1, s_k, d), lambda bi, hi: (bi, 0, hi))
        rowspec = pl.BlockSpec((1, s_q, LSE_LANES),
                               lambda bi, hi: (bi * h + hi, 0, 0))
        dq, dk, dv = pl.pallas_call(
            functools.partial(
                _bwd_single_tile_kernel, scale=scale, causal=causal,
                block_q=s_q, block_k=s_k, seq_q=s_q, seq_k=s_k,
                causal_offset=s_k - s_q,
            ),
            grid=(b, h),
            in_specs=[spec, kspec, kspec, spec, rowspec, rowspec],
            out_specs=[spec, kspec, kspec],
            out_shape=[
                jax.ShapeDtypeStruct((b, s_q, e), q.dtype),
                jax.ShapeDtypeStruct((b, s_k, e), k.dtype),
                jax.ShapeDtypeStruct((b, s_k, e), v.dtype),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
            ),
            interpret=interpret,
            name="flash_attention_bwd_fused_packed",
        )(q, k, v, g, lse, delta)
        return dq, dk, dv
    qspec = pl.BlockSpec((1, bq, d), lambda bi, hi, i, j: (bi, i, hi))
    kspec = pl.BlockSpec((1, bk, d), lambda bi, hi, i, j: (bi, j, hi))
    rowspec = pl.BlockSpec((1, bq, LSE_LANES),
                           lambda bi, hi, i, j: (bi * h + hi, i, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, nj=nj, i_dim=2, j_dim=3, **common),
        grid=(b, h, ni, nj),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b, s_q, e), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_bwd_dq_packed",
    )(q, k, v, g, lse, delta)
    # kv-grid kernels: block index maps take (b, h, kv_j, q_i)
    qspec2 = pl.BlockSpec((1, bq, d), lambda bi, hi, j, i: (bi, i, hi))
    kspec2 = pl.BlockSpec((1, bk, d), lambda bi, hi, j, i: (bi, j, hi))
    rowspec2 = pl.BlockSpec((1, bq, LSE_LANES),
                            lambda bi, hi, j, i: (bi * h + hi, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, ni=ni, nj=nj, i_dim=3, j_dim=2,
                          **common),
        grid=(b, h, nj, ni),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowspec2, rowspec2],
        out_specs=[kspec2, kspec2],
        out_shape=[
            jax.ShapeDtypeStruct((b, s_k, e), k.dtype),
            jax.ShapeDtypeStruct((b, s_k, e), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_bwd_dkv_packed",
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_packed(q, k, v, num_heads, causal, scale, block_q, block_k):
    out, _ = _flash_fwd_packed(q, k, v, num_heads, causal, scale,
                               block_q, block_k, save_lse=False)
    return out


def _flash_packed_vjp_fwd(q, k, v, num_heads, causal, scale,
                          block_q, block_k):
    out, lse = _flash_fwd_packed(q, k, v, num_heads, causal, scale,
                                 block_q, block_k)
    return out, (q, k, v, out, lse)


def _flash_packed_vjp_bwd(num_heads, causal, scale, block_q, block_k,
                          res, g):
    q, k, v, out, lse = res
    return _flash_bwd_packed(q, k, v, out, lse, g, num_heads, causal,
                             scale, block_q, block_k)


_flash_packed.defvjp(_flash_packed_vjp_fwd, _flash_packed_vjp_bwd)


def flash_attention_packed(
    q, k, v, *, num_heads: int, causal: bool = False,
    scale: float | None = None, block_q: int = 512, block_k: int = 512,
):
    """Fused attention on (batch, seq, heads·head_dim) activations — the
    qkv projection's natural layout, so no head transpose is ever
    materialized. Numerics identical to flash_attention on the transposed
    layout (same kernel bodies). Shapes the kernel can't tile fall back to
    the XLA path via an explicit (cheap at those sizes) transpose."""
    b, s_q, e = q.shape
    s_k = k.shape[1]
    d = e // num_heads
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if e % num_heads != 0:
        raise ValueError(f"embed dim {e} % heads {num_heads} != 0")
    # Mosaic requires the LAST block dim be a multiple of 128 or the full
    # array width (lowering.py _check_block_mappings). head_dim % 128 == 0
    # satisfies it with one head per block; NARROWER heads now satisfy it
    # too via head-GROUP blocks (hpb heads per 128-lane stripe, or the
    # full array width) with an in-kernel static head loop — so head_dim
    # 64 models run relayout-free where they previously paid the
    # transposed-layout copies. Shapes the gate refuses take the
    # transposed entry point, whose identical gate names itself and runs
    # the XLA reference.
    if _shape_gate(s_q, s_k, d, causal) is not None:
        def split(t, s):
            return t.reshape(b, s, num_heads, d).transpose(0, 2, 1, 3)

        out = flash_attention(split(q, s_q), split(k, s_k), split(v, s_k),
                              causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k)
        return out.transpose(0, 2, 1, 3).reshape(b, s_q, e)
    return _flash_packed(q, k, v, num_heads, causal, scale,
                         block_q, block_k)


# --------------------------------------------------------- decode (q_len=1)
# Serving's hot path: ONE new query row per slot attending over that slot's
# KV cache rows [0, length). The kernel is a degenerate flash forward —
# grid (slots, heads, kv-blocks), a (1, block_k) logits stripe, online
# softmax carried in VMEM — with the causal mask replaced by a per-slot
# LENGTH mask (key_pos < length), since cache rows past the slot's cursor
# hold stale garbage from earlier residents of the slot. Dead kv blocks
# (entirely past the cursor) are skipped, so a nearly-empty cache costs
# O(length), not O(max_seq). Like the packed training kernel, q/k/v stay in
# the (slots, seq, heads·head_dim) projection layout — heads are selected
# by lane-offset block index maps, no head transpose touches HBM.


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *refs,
                   scale: float, block_k: int, seq_k: int, nj: int):
    if nj == 1:
        m_ref = l_ref = acc_ref = None
    else:
        m_ref, l_ref, acc_ref = refs
    j = pl.program_id(2)
    length = len_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        if nj > 1:
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

    def step():
        q = q_ref[0]  # (1, d)
        k = k_ref[0]  # (block_k, d)
        v = v_ref[0]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (1, block_k)
        key_pos = jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1) + j * block_k
        mask = key_pos < length
        logits = jnp.where(mask, logits, NEG_INF)
        # zero masked V rows: stale cache rows can hold anything (NaN in
        # interpret mode) and 0·NaN would poison the contraction
        v = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            + j * block_k < length, v, 0.0)
        if nj == 1:
            m = logits.max(axis=-1)
            p = jnp.exp(logits - m[:, None])
            l = p.sum(axis=-1)
            acc = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            # length == 0 (empty slot) ⇒ l == 0; clamp keeps the dead row
            # finite (its output is never consumed) without touching live
            # rows, whose l >= exp(0) = 1
            o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(
                o_ref.dtype)
        else:
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[:, None])
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
            acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[...] = m_new

    if nj == 1:
        step()
        return
    # live ⇔ the block's first key is inside [0, length)
    pl.when(j * block_k < length)(step)

    @pl.when(j == nj - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(
                        o_ref.dtype)


def decode_attention_reference(q, k, v, positions, *, num_heads: int,
                               scale: float | None = None):
    """Reference einsum attention over a KV cache — the CPU serving path
    and the decode kernel's numerics oracle. q: (slots, q_len, H·hd) new
    queries, k/v: (slots, S, H·hd) cache (new rows already written),
    positions: (slots, q_len) int32 absolute position of each query row.
    Query row i attends cache rows [0, positions[s, i]] — intra-chunk
    causality during prefill falls out of the per-row positions. Same
    where(-1e30)/softmax convention as sdpa_xla, so greedy decode is
    token-identical to the teacher-forced training forward. The
    speculative verify call (serving/speculative.py) rides the SAME
    multi-query path at q_len=K+1 — each proposal row's logits equal
    what plain decode would compute after the rows before it, which is
    the whole bit-identity argument; the Pallas kernels below stay
    q_len=1, so multi-query calls (prefill chunks and verify alike)
    take this einsum on every backend — a multi-query Pallas decode
    kernel is the ROADMAP item that would close the gap."""
    slots, q_len, e = q.shape
    s_k = k.shape[1]
    h = num_heads
    d = e // h
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    def split(t, s):
        return t.reshape(slots, s, h, d).transpose(0, 2, 1, 3)

    qh, kh, vh = split(q, q_len), split(k, s_k), split(v, s_k)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                        preferred_element_type=jnp.float32)
    logits = logits * scale
    key_pos = jnp.arange(s_k, dtype=jnp.int32)
    mask = key_pos[None, None, None, :] <= positions[:, None, :, None]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return out.transpose(0, 2, 1, 3).reshape(slots, q_len, e)


def _decode_gate(rows: int, d: int, num_heads: int,
                 interpret: bool) -> str | None:
    """The decode kernels' shared shape gate, by name. Heads are selected
    by lane offset, which Mosaic allows only for head_dim % 128 == 0 (see
    flash_attention_packed; the interpreter has no such rule), and small
    caches aren't worth a kernel launch anywhere."""
    if rows < 128:
        return f"cache rows {rows} < 128"
    if d % 128 != 0 and num_heads != 1 and not interpret:
        return f"head_dim {d} % 128 != 0"
    return None


def flash_decode_attention(
    q, k, v, lengths, *, num_heads: int, scale: float | None = None,
    block_k: int = 512,
):
    """Single-query decode attention on the packed layout. q: (slots, 1,
    H·hd), k/v: (slots, S, H·hd) cache, lengths: (slots,) int32 live-key
    counts (query at position p attends p+1 keys). Shapes the kernel can't
    tile on hardware (_decode_gate) take the reference einsum, with a
    KernelFallbackWarning on a TPU — the serving op routes CPU meshes
    there directly, so tier-1 exercises serving without Pallas."""
    slots, q_len, e = q.shape
    if q_len != 1:
        raise ValueError(f"decode kernel is single-query (got q_len={q_len})")
    s_k = k.shape[1]
    d = e // num_heads
    if e % num_heads != 0:
        raise ValueError(f"embed dim {e} % heads {num_heads} != 0")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    interpret = jax.default_backend() != "tpu"
    gate = _decode_gate(s_k, d, num_heads, interpret)
    if gate is not None:
        warn_reference("flash_decode_attention", (q.shape, k.shape), gate)
        positions = (lengths.astype(jnp.int32) - 1)[:, None]
        return decode_attention_reference(q, k, v, positions,
                                          num_heads=num_heads, scale=scale)
    bk = min(block_k, s_k)
    nj = pl.cdiv(s_k, bk)
    # the per-slot lengths ride scalar prefetch (SMEM), like the paged
    # kernel's: a (1, lanes) stripe block over a (slots, lanes) array has
    # a second-minor block of 1, which Mosaic refuses
    qspec = pl.BlockSpec((1, 1, d), lambda s, h, j, ln: (s, 0, h))
    kspec = pl.BlockSpec((1, bk, d), lambda s, h, j, ln: (s, j, h))
    scratch_shapes = []
    if nj > 1:
        scratch_shapes = [
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(slots, num_heads, nj),
        in_specs=[qspec, kspec, kspec],
        out_specs=qspec,
        scratch_shapes=scratch_shapes,
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block_k=bk,
                          seq_k=s_k, nj=nj),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, 1, e), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_decode",
    )(lengths.astype(jnp.int32), q, k, v)
    return out


# ------------------------------------------------------- paged decode
# Serving's paged hot path (vLLM/PagedAttention): the KV cache is a block
# pool (num_blocks, block_size, H·hd) shared by every slot, and each slot
# reads its cache THROUGH a page table (slots, blocks_per_slot) int32. The
# kernel is the single-query decode kernel with the kv grid axis walking
# the page table instead of a contiguous cache: the K/V BlockSpec index
# maps read the physical block id from the scalar-prefetched table
# (PrefetchScalarGridSpec), so the gather costs nothing beyond the DMA the
# contiguous kernel already issues — and the dead-block skip is preserved
# (logical blocks past the slot's cursor are never fetched; their table
# entries point at the scratch block and the `pl.when` guard skips them).


def _paged_decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         *refs, scale: float, block_size: int, nj: int):
    if nj == 1:
        m_ref = l_ref = acc_ref = None
    else:
        m_ref, l_ref, acc_ref = refs
    j = pl.program_id(2)
    s = pl.program_id(0)
    length = len_ref[s]

    @pl.when(j == 0)
    def _init():
        if nj > 1:
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

    def step():
        q = q_ref[0]  # (1, d)
        k = k_ref[0]  # (block_size, d) — physical block tbl[s, j]
        v = v_ref[0]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (1, block_size)
        # LOGICAL key position of row r in this block is j*block_size + r
        # (the table maps logical→physical; the logical axis is what the
        # per-slot length masks)
        key_pos = jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1) + j * block_size
        logits = jnp.where(key_pos < length, logits, NEG_INF)
        # zero masked V rows: rows past the cursor in a partially-filled
        # block hold stale pool state (NaN in interpret mode)
        v = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            + j * block_size < length, v, 0.0)
        if nj == 1:
            m = logits.max(axis=-1)
            p = jnp.exp(logits - m[:, None])
            l = p.sum(axis=-1)
            acc = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(
                o_ref.dtype)
        else:
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[:, None])
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
            acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_ref[...] = m_new

    if nj == 1:
        step()
        return
    # dead-block skip: a logical block entirely past the cursor is never
    # computed (its physical block — usually scratch — may still DMA; the
    # table keeps unallocated entries at scratch so that DMA is one hot
    # block, not a cold pool walk)
    pl.when(j * block_size < length)(step)

    @pl.when(j == nj - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(
                        o_ref.dtype)


def paged_decode_attention_reference(q, pool_k, pool_v, page_table,
                                     positions, *, num_heads: int,
                                     scale: float | None = None):
    """Einsum oracle for the paged decode kernel (and the CPU serving
    path, via ops/inc_attention.py): gather each slot's logical cache
    view from the pool through its page table, then run the contiguous
    reference. q: (slots, q_len, H·hd); pool_k/v: (num_blocks, bs, H·hd);
    page_table: (slots, W) int32; positions: (slots, q_len) int32 (query
    row i attends logical rows [0, positions[s, i]]; negative = dead)."""
    slots = q.shape[0]
    W = page_table.shape[1]
    bs = pool_k.shape[1]
    e = pool_k.shape[-1]
    kc = pool_k[page_table].reshape(slots, W * bs, e).astype(q.dtype)
    vc = pool_v[page_table].reshape(slots, W * bs, e).astype(q.dtype)
    return decode_attention_reference(q, kc, vc, positions,
                                      num_heads=num_heads, scale=scale)


def paged_flash_decode_attention(
    q, pool_k, pool_v, page_table, lengths, *, num_heads: int,
    scale: float | None = None,
):
    """Single-query decode attention over a paged KV pool. q: (slots, 1,
    H·hd); pool_k/v: (num_blocks, block_size, H·hd); page_table: (slots,
    W) int32 logical→physical block map; lengths: (slots,) int32 live-key
    counts. The kv grid walks the page table via scalar prefetch — one
    (1, block_size, head) K/V block DMA per live logical block, dead
    blocks skipped. Shapes the kernel can't tile on hardware take the
    gather + einsum reference, with a KernelFallbackWarning on a TPU (the
    CPU serving path routes there directly)."""
    slots, q_len, e = q.shape
    if q_len != 1:
        raise ValueError(f"decode kernel is single-query (got q_len={q_len})")
    bs = pool_k.shape[1]
    W = page_table.shape[1]
    d = e // num_heads
    if e % num_heads != 0:
        raise ValueError(f"embed dim {e} % heads {num_heads} != 0")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    interpret = jax.default_backend() != "tpu"
    # the contiguous kernel's gate + the paged-specific one: a block must
    # be a legal (sublane, lane) tile, so tiny block sizes route to the
    # reference
    gate = _decode_gate(W * bs, d, num_heads, interpret)
    if gate is None and bs % 8 != 0:
        gate = f"block_size {bs} % 8 != 0"
    if gate is not None:
        warn_reference("paged_flash_decode_attention",
                       (q.shape, pool_k.shape), gate)
        positions = (lengths.astype(jnp.int32) - 1)[:, None]
        return paged_decode_attention_reference(
            q, pool_k, pool_v, page_table, positions,
            num_heads=num_heads, scale=scale)
    nj = W
    lengths = lengths.astype(jnp.int32)
    table = page_table.astype(jnp.int32)
    qspec = pl.BlockSpec((1, 1, d), lambda s, h, j, tbl, ln: (s, 0, h))
    # the paged gather: the physical block row comes from the prefetched
    # table, not the grid index
    kspec = pl.BlockSpec(
        (1, bs, d), lambda s, h, j, tbl, ln: (tbl[s, j], 0, h))
    scratch_shapes = []
    if nj > 1:
        scratch_shapes = [
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, num_heads, nj),
        in_specs=[qspec, kspec, kspec],
        out_specs=qspec,
        scratch_shapes=scratch_shapes,
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale,
                          block_size=bs, nj=nj),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, 1, e), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_paged_decode",
    )(table, lengths, q, pool_k, pool_v)
    return out


def flash_attention(
    q, k, v, *, causal: bool = False, scale: float | None = None,
    block_q: int = 512, block_k: int = 512,
):
    """Fused attention. q,k,v: (batch, heads, seq, head_dim).

    Default 512-blocks: measured on v5e, one 512-wide kv block per q block
    (the one-pass-softmax specialization) beats smaller causal-skipping
    tilings — grid-iteration overhead outweighs the skipped exp work at
    short-to-medium sequence. At seq > 512 the kv axis tiles at 512 and the
    online-softmax path takes over."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    gate = _shape_gate(q.shape[2], k.shape[2], q.shape[3], causal)
    if gate is not None:
        warn_reference("flash_attention", (q.shape, k.shape), gate)
        return _attn_reference(q, k, v, causal, scale)
    return _flash(q, k, v, causal, scale, block_q, block_k)
