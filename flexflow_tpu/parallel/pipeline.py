"""Pipeline parallelism over the `pipe` mesh axis.

The reference leaves OP_PIPELINE as an enum with no implementation
(ffconst.h:159, SURVEY §2.3) — this module EXCEEDS reference capability with
a working microbatched pipeline: L homogeneous blocks (stacked weights,
leading dim L) are split into P = |pipe| stages; inside `shard_map` each
stage holds its L/P layers, activations hop stage-to-stage via
`jax.lax.ppermute` over neighbor ICI links, and a `lax.scan` over
M + P - 1 ticks runs the classic fill/steady/drain schedule with M
microbatches in flight.

Schedule note: the forward is the GPipe fill-drain order; the backward is
its exact autodiff transpose (reverse fill-drain — ppermute's transpose
reverses the ring), so gradients are EXACT w.r.t. the unpipelined
computation. A literal 1F1B interleave of fwd/bwd microbatches (a
memory-scheduling refinement, not a numerics change) would need a custom
VJP schedule; activation memory is instead bounded the standard JAX way —
wrap `block_fn` in `jax.checkpoint` (pipeline_blocks does).

Invalid-slot routing: during fill/drain every stage still executes its
block on placeholder data (SPMD executes everywhere), but placeholder
outputs only ever reach placeholder slots and the final emission selects
valid microbatches, so numerics — forward and backward — match the
sequential computation exactly (verified in tests/test_pipeline.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..machine import AXIS_DATA, AXIS_PIPE

def _sequential(stacked, x, block_fn):
    """Reference semantics: apply the L stacked blocks in order."""
    def step(a, w_one):
        return block_fn(w_one, a), None

    out, _ = jax.lax.scan(step, x, stacked)
    return out


def _pipelined_local(stacked_shard, x, *, block_fn, axis_name: str,
                     num_stages: int, num_micro: int):
    """Per-stage body (inside shard_map). stacked_shard: this stage's
    (L/P, ...) weights; x: (b_local, ...) activations (replicated over the
    pipe axis)."""
    p_idx = jax.lax.axis_index(axis_name)
    b = x.shape[0]
    m = num_micro
    if b % m != 0:
        raise ValueError(
            f"pipeline: local batch {b} does not divide into "
            f"{m} microbatches (global batch must be a multiple of "
            f"data-axis size × num_microbatches)")
    mb = b // m
    mbs = x.reshape((m, mb) + x.shape[1:])

    def stage(a):
        def layer(a, w_one):
            return block_fn(w_one, a), None

        out, _ = jax.lax.scan(layer, a, stacked_shard)
        return out

    # stage p -> p+1 hops; stage 0 receives zeros (unused: it reads fresh
    # microbatches), the last stage's output leaves the ring via `emit`
    perm = [(i, i + 1) for i in range(num_stages - 1)]
    ticks = m + num_stages - 1

    def tick(buf, t):
        mb_idx = jnp.clip(t, 0, m - 1)
        my_in = jnp.where(p_idx == 0, mbs[mb_idx], buf)
        out = stage(my_in)
        nxt = jax.lax.ppermute(out, axis_name, perm)
        return nxt, out

    _, emits = jax.lax.scan(tick, jnp.zeros_like(mbs[0]),
                            jnp.arange(ticks))
    # the last stage's emissions at ticks P-1 .. P-1+M-1 are microbatches
    # 0 .. M-1; other stages' emissions are placeholder data
    y = emits[num_stages - 1:].reshape(x.shape)
    y = jax.lax.psum(
        jnp.where(p_idx == num_stages - 1, y, jnp.zeros_like(y)),
        axis_name,
    )
    return y


def pipeline_apply(
    stacked, x, block_fn, *,
    mesh: Mesh | None = None,
    num_microbatches: int = 0,
    axis_name: str = AXIS_PIPE,
    batch_axis: str = AXIS_DATA,
):
    """Apply L stacked homogeneous blocks to x, pipelined over `axis_name`
    when the mesh has one (falls back to the sequential scan otherwise —
    the two paths are numerically identical).

    stacked: pytree whose leaves all have leading dim L (block index);
    x: (batch, ...) global array; block_fn(one_block_weights, x) -> x'.
    num_microbatches 0 → 2·P (double-buffered steady state); the local
    batch must divide by it."""
    num_layers = jax.tree.leaves(stacked)[0].shape[0]
    if mesh is None or mesh.shape.get(axis_name, 1) <= 1:
        return _sequential(stacked, x, block_fn)
    p = mesh.shape[axis_name]
    if num_layers % p != 0:
        raise ValueError(
            f"pipeline: {num_layers} blocks do not divide over "
            f"{p} pipeline stages")
    m = num_microbatches or 2 * p

    w_spec = jax.tree.map(lambda _: P(axis_name), stacked)
    x_spec = P(batch_axis if mesh.shape.get(batch_axis, 1) > 1 else None)
    fn = jax.shard_map(
        functools.partial(
            _pipelined_local, block_fn=block_fn, axis_name=axis_name,
            num_stages=p, num_micro=m,
        ),
        mesh=mesh,
        in_specs=(w_spec, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )
    return fn(stacked, x)
