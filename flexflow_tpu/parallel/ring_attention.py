"""Ring attention: sequence/context parallelism over the ICI ring.

The reference has **no long-context support** (SURVEY §5: "no ring attention,
no Ulysses"); its only sequence notion is a seq_length iteration config. This
module provides the TPU-native capability the reference lacks: queries stay
resident on their sequence shard while K/V blocks rotate around the `seq`
mesh axis via `jax.lax.ppermute` (Ring Attention, Liu et al. 2023).

Round-7 roofline rewrite — the ring body is an explicitly DOUBLE-BUFFERED
ppermute pipeline:

  - the hop delivering block k+1 is issued BEFORE block k's attention
    compute, so the collective-permute has no data dependence on the
    compute and XLA's latency-hiding scheduler overlaps the two (the
    decomposition schedule of Wang et al., ASPLOS '23, expressed at the
    shard_map level). `overlap=False` restores the serial
    compute-then-hop order for ablation (bench.py's ring legs).
  - per-block attention routes through the flash/online-softmax kernel
    (`flash_attention_with_lse`) instead of a full materialized
    (b, h, s_loc, s_loc) f32 einsum — HBM traffic per block drops from
    O(s_loc²) to O(s_loc·d), the difference between roofline-bound and
    memory-bound at seq 4096.
  - block contributions merge by (out, lse) pairs:
    lse = logaddexp(lse, lse_blk), out = Σ out_blk·exp(lse_blk − lse) —
    the same online-softmax algebra the in-kernel accumulator uses,
    lifted to block granularity.
  - under a causal mask, ring blocks that originated on a LATER shard
    (src > idx ⇔ step > idx) are fully masked; their attention compute is
    skipped via `lax.cond` instead of masked to zero after the einsum —
    shard idx computes only idx+1 of the n blocks (~2× less work on
    average). The hop itself still runs every non-final step (it is a
    lockstep collective: later shards still need the block), and the
    final rotation — whose result no shard consumes — is skipped
    entirely.

Used by MultiHeadAttention(impl="ring") together with the
`sequence_parallel_attention` strategy (seq dim sharded over AXIS_SEQ).
The Unity cost model prices this op's ring traffic on an `overlappable`
comm channel — max(compute, comm) instead of compute + comm — so the
search sees the same overlap the schedule delivers (search/cost_model.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..machine import AXIS_DATA, AXIS_MODEL, AXIS_SEQ

def _block_attention(q, k_blk, v_blk, *, causal: bool, scale: float):
    """One ring block's attention: (out f32, lse f32) via the flash
    online-softmax kernel (Pallas on TPU, its einsum-lse fallback at
    shapes the kernel can't tile — including the small CPU test shards)."""
    from ..kernels.flash_attention import flash_attention_with_lse

    out, lse = flash_attention_with_lse(q, k_blk, v_blk, causal=causal,
                                        scale=scale)
    return out.astype(jnp.float32), lse


def _merge_block(o, lse, o_blk, lse_blk):
    """Online merge of a new block's (out, lse) into the running pair.
    With lse initialized to -inf the first merge reduces to (o_blk,
    lse_blk) exactly (exp(-inf − finite) == 0)."""
    lse_new = jnp.logaddexp(lse, lse_blk)
    o_new = (o * jnp.exp(lse - lse_new)[..., None]
             + o_blk * jnp.exp(lse_blk - lse_new)[..., None])
    return o_new, lse_new


def _ring_local(q, k, v, *, axis_name: str, n: int, causal: bool,
                scale: float, overlap: bool):
    """Per-shard body (inside shard_map). q,k,v: (b, h, s_loc, d) local.

    Unrolled over the `n` ring steps (n = seq-axis size, small and
    static). Double-buffered: the step-k hop is in flight while block k's
    flash attention runs (see module docstring)."""
    idx = jax.lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape

    from .ops import ring_permutation

    o = jnp.zeros((b, h, s_loc, d), jnp.float32)
    lse = jnp.full((b, h, s_loc), -jnp.inf, jnp.float32)
    perm = ring_permutation(n)
    k_blk, v_blk = k, v

    for step in range(n):
        k_nxt = v_nxt = None
        if overlap and step < n - 1:
            # issue the hop for block step+1 BEFORE computing block step:
            # the permute has no dependence on the compute below, so the
            # scheduler can run them concurrently (double buffering)
            k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
            v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        if not causal:
            o, lse = _merge_block(
                o, lse, *_block_attention(q, k_blk, v_blk, causal=False,
                                          scale=scale))
        elif step == 0:
            # the resident block (src == idx): the diagonal — the only
            # block that needs an in-block causal mask
            o, lse = _merge_block(
                o, lse, *_block_attention(q, k_blk, v_blk, causal=True,
                                          scale=scale))
        else:
            # block from src = (idx - step) mod n: fully live iff
            # src < idx ⇔ step <= idx, fully masked otherwise — skip the
            # compute entirely instead of masking it to zero afterwards
            def _live(o, lse, kb, vb):
                return _merge_block(
                    o, lse, *_block_attention(q, kb, vb, causal=False,
                                              scale=scale))

            def _dead(o, lse, kb, vb):
                return o, lse

            o, lse = jax.lax.cond(step <= idx, _live, _dead,
                                  o, lse, k_blk, v_blk)
        if step < n - 1:
            if not overlap:
                # serial ablation baseline: hop only after the compute
                k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
                v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
            k_blk, v_blk = k_nxt, v_nxt
        # the final rotation (step == n-1) — whose result no shard would
        # consume — is never issued

    return o.astype(q.dtype)


def ring_attention(
    q, k, v, *, causal: bool = False, scale: float | None = None,
    mesh: Mesh | None = None, axis_name: str = AXIS_SEQ,
    batch_axis: str = AXIS_DATA, head_axis: str = AXIS_MODEL,
    overlap: bool = True,
):
    """Exact attention with the seq dim sharded over `axis_name`.

    q,k,v: (batch, heads, seq, head_dim) global arrays (call under jit).
    Falls back to single-shard attention when no mesh / seq axis size 1.
    `overlap=False` disables the double-buffered hop issue (ablation)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if mesh is None or mesh.shape.get(axis_name, 1) == 1:
        from ..ops.attention import sdpa_xla

        return sdpa_xla(q, k, v, causal=causal, scale=scale)

    from .. import telemetry

    n = mesh.shape[axis_name]
    # trace-time breadcrumb: one event per compiled ring-attention op, so
    # telemetry shows which compiles carry the overlapped schedule (the
    # long-context CI smoke asserts on it)
    telemetry.event("ring.attention", steps=n, overlap=bool(overlap),
                    causal=bool(causal), seq=int(q.shape[2]))

    spec = P(
        batch_axis if mesh.shape.get(batch_axis, 1) > 1 else None,
        head_axis if mesh.shape.get(head_axis, 1) > 1 else None,
        axis_name,
        None,
    )
    fn = jax.shard_map(
        functools.partial(
            _ring_local, axis_name=axis_name, n=n,
            causal=causal, scale=scale, overlap=overlap,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
