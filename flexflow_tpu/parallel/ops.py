"""Parallelization operators: Repartition, Combine, Replicate, Reduction,
FusedParallelOp, Pipeline.

Reference: src/parallel_ops/{partition,combine,replicate,reduction,
fused_parallel_op}.cc — each is a PCG node that changes a tensor's
parallelization state (per-dim degree / replica dims) and whose execution is
data movement (Legion partition copies, SURVEY §2.3).

TPU-native lowering: the *runtime* body of every parallel op is the identity —
the executor pins each node's output with `with_sharding_constraint`, so the
degree change becomes an XLA collective over ICI exactly where the reference
would launch a partition-copy task:

  Repartition (degree up on dim d)  → resharding: dynamic-slice / all_to_all
  Combine     (degree down on dim d)→ all_gather along the freed mesh axis
  Replicate   (new replica dim)     → broadcast (implicit in GSPMD)
  Reduction   (drop replica dim)    → psum / reduce_scatter (inserted by XLA
                                      when the producer's contraction was
                                      sharded over the reduced axis)

The *IR-level* shape transform (apply_parallel_op_shape) is what Unity search
rewrites operate on, and the cost model charges the communication bytes these
transforms imply (see search/cost_model.py).

The reference leaves OP_PIPELINE as an enum with no implementation
(ffconst.h:159, SURVEY §2.3); here PipelineParams is likewise a stage
MARKER only (runtime identity — enum parity). Working pipeline parallelism
lives in the OP_PIPE_BLOCKS op instead: stacked homogeneous blocks whose
layer dim shards over the `pipe` mesh axis, scheduled as a
`jax.lax.ppermute` fill/drain microbatch pipeline (parallel/pipeline.py) —
the capability the reference never implemented.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Tuple

from ..fftype import OperatorType as OT
from ..tensor import ParallelDim, ParallelTensorShape
from ..ops.base import OpDef, register_op


@dataclass(frozen=True)
class RepartitionParams:
    """Increase partition degree along `dim` by `degree`×
    (partition.cc:132 create_input_partition). `axes` optionally names the
    mesh axes the new degree rides (their size product must equal
    `degree`) — the MachineView device binding; empty = inferred from the
    degree at assignment time."""

    dim: int
    degree: int
    axes: Tuple[str, ...] = ()


@dataclass(frozen=True)
class CombineParams:
    """Decrease partition degree along `dim` by `degree`× (combine.cc:135).
    `axes` optionally names the mesh axes being freed."""

    dim: int
    degree: int
    axes: Tuple[str, ...] = ()


@dataclass(frozen=True)
class ReplicateParams:
    """Add a replica dim of extent `degree` (replicate.cc). `axes`
    optionally names the mesh axes the replicas map onto."""

    degree: int
    axes: Tuple[str, ...] = ()


@dataclass(frozen=True)
class ReductionParams:
    """Sum-reduce a replica dim of extent `degree` (reduction.cc: forward
    kernel sums num_replicas slices — here XLA's psum). `axes` optionally
    names the mesh axes summed over."""

    degree: int
    axes: Tuple[str, ...] = ()


@dataclass(frozen=True)
class PipelineParams:
    """Stage boundary marker. OP_PIPELINE is enum-only in the reference."""

    stage: int = 0


@dataclass(frozen=True)
class ParallelOpInfo:
    op_type: OT
    dim: int
    degree: int
    axes: Tuple[str, ...] = ()


@dataclass(frozen=True)
class FusedParallelOpParams:
    """Sequence of parallel transforms fused into one resharding
    (fused_parallel_op.cc)."""

    ops: Tuple[ParallelOpInfo, ...]


def apply_parallel_op_shape(
    shape: ParallelTensorShape, op_type: OT, params
) -> ParallelTensorShape:
    """IR shape transform for one parallel op (search rewrites use this)."""
    dims = list(shape.dims)
    axes = getattr(params, "axes", ())
    if op_type == OT.OP_REPARTITION:
        d = dims[params.dim]
        dims[params.dim] = replace(d, degree=d.degree * params.degree,
                                   axes=d.axes + tuple(axes))
    elif op_type == OT.OP_COMBINE:
        d = dims[params.dim]
        if d.degree % params.degree != 0:
            raise ValueError(
                f"combine degree {params.degree} does not divide {d.degree}"
            )
        new_axes = d.axes
        if axes and new_axes[-len(axes):] == tuple(axes):
            new_axes = new_axes[:-len(axes)]
        elif d.degree // params.degree == 1:
            new_axes = ()
        dims[params.dim] = replace(d, degree=d.degree // params.degree,
                                   axes=new_axes)
    elif op_type == OT.OP_REPLICATE:
        dims.append(
            ParallelDim(
                size=params.degree, degree=params.degree,
                is_replica_dim=True, axes=tuple(axes)
            )
        )
    elif op_type == OT.OP_REDUCTION:
        for i in range(len(dims) - 1, -1, -1):
            if dims[i].is_replica_dim:
                if dims[i].degree != params.degree:
                    raise ValueError(
                        f"reduction degree {params.degree} != replica degree "
                        f"{dims[i].degree}"
                    )
                dims.pop(i)
                break
        else:
            raise ValueError("reduction with no replica dim")
    elif op_type == OT.OP_FUSED_PARALLEL:
        s = shape
        for info in params.ops:
            sub = _INFO_PARAMS[info.op_type](info)
            s = apply_parallel_op_shape(s, info.op_type, sub)
        return s
    elif op_type == OT.OP_PIPELINE:
        pass
    else:
        raise ValueError(f"not a parallel op: {op_type}")
    return ParallelTensorShape(tuple(dims), shape.dtype)


_INFO_PARAMS = {
    OT.OP_REPARTITION: lambda i: RepartitionParams(i.dim, i.degree, i.axes),
    OT.OP_COMBINE: lambda i: CombineParams(i.dim, i.degree, i.axes),
    OT.OP_REPLICATE: lambda i: ReplicateParams(i.degree, i.axes),
    OT.OP_REDUCTION: lambda i: ReductionParams(i.degree, i.axes),
}


def _identity_infer(params, in_shapes):
    return [in_shapes[0]]


def _identity_forward(params, inputs, weights, state, ctx):
    # Runtime body is the identity: the executor's sharding constraint on the
    # node's output performs the actual resharding (ICI collective).
    return [inputs[0]], state


def _zero_flops(params, in_shapes, out_shapes):
    return 0.0


for _ot in (
    OT.OP_REPARTITION,
    OT.OP_COMBINE,
    OT.OP_REPLICATE,
    OT.OP_REDUCTION,
    OT.OP_PIPELINE,
    OT.OP_FUSED_PARALLEL,
):
    register_op(
        OpDef(_ot, _identity_infer, _identity_forward, flops=_zero_flops)
    )


def ring_permutation(n: int) -> list:
    """THE ring-rotation schedule: shard i sends to (i+1) mod n — a
    complete bijection on range(n). Every ring body (ring attention's KV
    rotation, the decomposed allgather-matmul, the ring reduce-scatter,
    the ppermute hop calibrator) builds its ppermute permutation through
    this ONE helper, and the ffcheck collective-uniformity pass
    (analysis/collectives.py) validates exactly this function's output
    for every ring the plan will run — a partial or duplicated
    permutation would make ppermute zero-fill the missing destinations
    and silently corrupt the ring. (The pipeline fill/drain shift in
    parallel/pipeline.py is deliberately NOT a ring and does not use
    this.)"""
    return [(i, (i + 1) % n) for i in range(n)]


# ------------------------------------------------- decomposed collective matmul
# The async/overlapped twin of the tp all_gather→matmul pairs GSPMD inserts
# when a feature-sharded activation feeds an op expecting the full feature
# dim (tp_col after a feat/sp producer, the attention O-projection after a
# head-sharded core). Instead of one blocking all_gather followed by one
# big matmul, the gather is DECOMPOSED into n−1 neighbor hops each
# overlapped with the partial matmul of the block already resident (Wang
# et al., ASPLOS '23 — the same double-buffered ppermute schedule as
# parallel/ring_attention.py): while x's block k rotates to the neighbor,
# the local MXU contracts block k against the matching rows of w. Exact:
# after n steps every shard has accumulated Σ_src x_src @ w[src rows] =
# (all_gather(x) @ w), with the collective entirely hidden behind compute
# when the per-block matmul dominates the hop (the long-seq regime).


def _ag_matmul_local(x_blk, w, *, axis_name: str, n: int, overlap: bool):
    """Per-shard body: x_blk (..., k/n) is this shard's block of the
    contraction dim; w (k, m) holds all rows locally. Rotate x blocks
    around the ring, contracting each against its source's row slice."""
    import jax
    import jax.numpy as jnp

    idx = jax.lax.axis_index(axis_name)
    k_loc = x_blk.shape[-1]
    acc = jnp.zeros(x_blk.shape[:-1] + (w.shape[-1],), jnp.float32)
    perm = ring_permutation(n)
    for step in range(n):
        x_nxt = None
        if overlap and step < n - 1:
            # hop for block step+1 issued BEFORE the matmul of block step
            x_nxt = jax.lax.ppermute(x_blk, axis_name, perm)
        # the block held at `step` originated on shard (idx - step) mod n;
        # contract it against that shard's rows of w
        src = jax.lax.rem(idx - step + n, n)
        w_rows = jax.lax.dynamic_slice_in_dim(w, src * k_loc, k_loc, axis=0)
        acc = acc + jnp.dot(x_blk, w_rows.astype(x_blk.dtype),
                            preferred_element_type=jnp.float32)
        if step < n - 1:
            if not overlap:
                x_nxt = jax.lax.ppermute(x_blk, axis_name, perm)
            x_blk = x_nxt
    return acc.astype(x_blk.dtype)


def allgather_matmul(x, w, *, mesh=None, axis_name: str | None = None,
                     batch_axis: str | None = None, overlap: bool = True):
    """Decomposed all_gather→matmul: `x` (..., k) with its last dim sharded
    over `axis_name`, `w` (k, m) replicated along that axis; returns the
    full x @ w (replicated over `axis_name`, batch sharding preserved) —
    numerically the gathered matmul, scheduled as n overlapped
    block-matmul + ppermute steps. Falls back to a plain dot when there is
    no mesh / the axis has size 1. `overlap=False` is the serial ablation
    baseline (hop after each block's matmul)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..machine import AXIS_DATA, AXIS_MODEL
    axis_name = axis_name or AXIS_MODEL
    batch_axis = batch_axis or AXIS_DATA
    if mesh is None or mesh.shape.get(axis_name, 1) == 1:
        return jnp.dot(x, w.astype(x.dtype),
                       preferred_element_type=jnp.float32).astype(x.dtype)
    n = mesh.shape[axis_name]
    if x.shape[-1] % n != 0:
        raise ValueError(
            f"allgather_matmul: contraction dim {x.shape[-1]} not "
            f"divisible by axis {axis_name!r} size {n}")
    import functools

    nd = x.ndim
    b_entry = batch_axis if mesh.shape.get(batch_axis, 1) > 1 else None
    xspec = P(b_entry, *([None] * (nd - 2)), axis_name)
    ospec = P(b_entry, *([None] * (nd - 1)))
    fn = jax.shard_map(
        functools.partial(_ag_matmul_local, axis_name=axis_name, n=n,
                          overlap=overlap),
        mesh=mesh,
        in_specs=(xspec, P(None, None)),
        out_specs=ospec,
        check_vma=False,
    )
    return fn(x, w)


# ------------------------------------------------- weight-update sharding
# ZeRO (Rajbhandari et al., SC '20) / TPU weight-update sharding (Xu et
# al., 2020): every data-parallel replica redundantly stores fp32 masters
# + optimizer slots and redundantly runs the identical update. Sharding
# the update 1/dp along the gradient-reduction axes keeps the math
# bit-identical (the same reduced gradient elements feed the same
# element-wise update — each replica just owns a slice) while optimizer
# state shrinks by the replica count and the grad all-reduce splits into
# an overlappable reduce-scatter + a deferred all-gather. The helpers
# below are the ONE shared definition of "which dim shards over which
# axes" — the executor's placement, the cost model's memory/comm pricing,
# and the tests all resolve through them so runtime and search cannot
# disagree.


def choose_update_dim(shape, assignment, axes, axis_sizes) -> Optional[int]:
    """The dim of a weight `shape` to shard for the ZeRO-style update, or
    None when no dim is shardable. `assignment` is the weight's existing
    per-dim axis assignment (tuples of mesh-axis names), `axes` the update
    axes (the axes the gradient is reduced over). Picks the FIRST dim
    whose size divides by (existing degree × update degree) — first, not
    largest, so the choice is a deterministic function of the spec alone.
    Weights already sharded over any update axis are skipped (their
    optimizer state is already distributed along it)."""
    deg = 1
    for ax in axes:
        deg *= axis_sizes.get(ax, 1)
    if deg <= 1:
        return None
    used = {ax for entry in (assignment or ()) for ax in entry}
    if used.intersection(axes):
        return None
    for i, size in enumerate(shape):
        have = 1
        if assignment and i < len(assignment):
            for ax in assignment[i]:
                have *= axis_sizes.get(ax, 1)
        if size % (have * deg) == 0:
            return i
    return None


def grad_sync_axes(out_axes, weight_axes) -> Tuple[str, ...]:
    """The mesh axes a trainable weight's gradient is reduced over: every
    axis its consumers' activations shard that the weight itself does not
    (the axes the NCCL allreduce of optimizer_kernel.cu:78-110 spans) —
    sorted, so executor placement and cost-model pricing compose the same
    PartitionSpec entry."""
    return tuple(sorted(set(out_axes) - set(weight_axes)))


def _spec_assignment(spec, ndim):
    """PartitionSpec (or None) → per-dim axis tuples."""
    entries = []
    for i in range(ndim):
        e = spec[i] if spec is not None and i < len(spec) else None
        if e is None:
            entries.append(())
        elif isinstance(e, (tuple, list)):
            entries.append(tuple(e))
        else:
            entries.append((e,))
    return tuple(entries)


def weight_update_spec(shape, base_spec, axes, axis_sizes):
    """PartitionSpec of a weight's fp32 master / grad / optimizer slots
    under weight-update sharding: `base_spec` (the plan's compute
    placement) with the update `axes` appended onto the dim
    `choose_update_dim` picks. None when the weight is not shardable
    (stays replicated — partial coverage is fine; the update there is the
    replicated baseline, still bit-identical)."""
    from jax.sharding import PartitionSpec

    assignment = _spec_assignment(base_spec, len(shape))
    dim = choose_update_dim(shape, assignment, axes, axis_sizes)
    if dim is None:
        return None
    entries = []
    for i, entry in enumerate(assignment):
        merged = entry + tuple(axes) if i == dim else entry
        if not merged:
            entries.append(None)
        elif len(merged) == 1:
            entries.append(merged[0])
        else:
            entries.append(tuple(merged))
    return PartitionSpec(*entries)


def _rs_local(x, *, axis_name: str, n: int, overlap: bool):
    """Per-shard ring reduce-scatter body: `x` (m, ...) is this shard's
    full local contribution; returns the (m/n, ...) chunk this shard owns
    of the cross-shard sum. The packet destined for chunk c starts on
    shard (c+1) mod n and travels n−1 hops, accumulating each host's
    local chunk c — the double-buffered idiom of
    parallel/ring_attention.py: each hop has no data dependence on the
    local chunk slice/add beside it, so the latency-hiding scheduler
    overlaps them. `overlap=False` is the serial hop-THEN-add ablation —
    forced with an optimization barrier, because XLA schedules by data
    dependence, not trace order (merely reordering the statements would
    compile to the identical program)."""
    import jax

    idx = jax.lax.axis_index(axis_name)
    m = x.shape[0]
    chunk = m // n
    perm = ring_permutation(n)

    def take(src, c):
        return jax.lax.dynamic_slice_in_dim(src, c * chunk, chunk, axis=0)

    acc = take(x, jax.lax.rem(idx - 1 + n, n))
    for t in range(1, n):
        moved = jax.lax.ppermute(acc, axis_name, perm)
        src = x
        if not overlap:
            # serialize: the barrier makes the local slice depend on the
            # hop's arrival, so the add cannot issue behind the permute
            moved, src = jax.lax.optimization_barrier((moved, x))
        acc = moved + take(src, jax.lax.rem(idx - 1 - t + 2 * n, n))
    return acc


def ring_reduce_scatter(x, *, mesh=None, axis_name: str | None = None,
                        overlap: bool = True):
    """Decomposed reduce-scatter over `axis_name`: `x` (n·m, ...) holds
    each shard's full local contribution along dim 0 (sharded n-ways);
    returns the (m, ...) cross-shard sum scattered along the same axis —
    the explicit overlappable twin of the reduce-scatter GSPMD emits for
    the sharded weight update, scheduled as n−1 double-buffered ppermute
    hops (the grad-sync ablation in bench.py measures exactly this
    schedule against the serial one). Falls back to a plain psum-free
    identity when there is no mesh / the axis has size 1."""
    import functools

    import jax
    from jax.sharding import PartitionSpec as P

    from ..machine import AXIS_DATA
    axis_name = axis_name or AXIS_DATA
    if mesh is None or mesh.shape.get(axis_name, 1) == 1:
        return x
    n = mesh.shape[axis_name]
    if x.shape[0] % (n * n) != 0:
        raise ValueError(
            f"ring_reduce_scatter: dim 0 of {x.shape} must divide by "
            f"{axis_name!r} size {n} twice (local chunking)")
    nd = x.ndim
    fn = jax.shard_map(
        functools.partial(_rs_local, axis_name=axis_name, n=n,
                          overlap=overlap),
        mesh=mesh,
        in_specs=(P(axis_name, *([None] * (nd - 1))),),
        out_specs=P(axis_name, *([None] * (nd - 1))),
        check_vma=False,
    )
    return fn(x)


def all_gather(x, *, mesh=None, axis_name: str | None = None, dim: int = 0,
               in_spec=None, out_spec=None):
    """`x` sharded along `dim` over `axis_name` -> the full array
    replicated over that axis: XLA's own all-gather, asked for by name in
    a shard_map so that no choice of GSPMD's stands between a stage-3
    weight and its compute placement. Exact data movement.

    `in_spec`/`out_spec` optionally carry the tensor's OTHER mesh axes
    through the shard_map unchanged (a weight whose update dim merges
    ('model', 'data') gathers only 'data'; the update axes sit minor on
    the dim — weight_update_spec appends them — so chunks concatenate in
    shard order within each outer shard). The identity when there is no
    mesh / the axis has size 1."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..machine import AXIS_DATA
    axis_name = axis_name or AXIS_DATA
    if mesh is None or mesh.shape.get(axis_name, 1) == 1:
        return x
    nd = x.ndim
    if in_spec is None:
        in_spec = P(*([None] * dim), axis_name, *([None] * (nd - dim - 1)))
    if out_spec is None:
        out_spec = P(*([None] * nd))
    fn = jax.shard_map(
        lambda shard: jax.lax.all_gather(shard, axis_name, axis=dim,
                                         tiled=True),
        mesh=mesh,
        in_specs=(in_spec,),
        out_specs=out_spec,
        check_vma=False,
    )
    return fn(x)


def derive_parallel_assignment(op_type: OT, params, in_assignment, mesh):
    """Mesh-axis assignment for an explicit parallel-op node's output, derived
    from its input's assignment (the runtime half of the op: the executor pins
    the output with this spec, producing the resharding collective).

    Repartition picks the first mesh axis whose size equals the requested
    degree and which the tensor doesn't already use — the analog of the
    mapper choosing fresh devices for a higher-degree machine view."""
    a = [list(x) for x in in_assignment]
    declared = tuple(getattr(params, "axes", ()))
    if op_type == OT.OP_REPARTITION:
        if declared:
            # the rewrite named its axes (MachineView binding): use them —
            # but a mesh axis may shard a tensor at most once (same check
            # as the inference path's "unused axis" scan)
            used = {ax for entry in a for ax in entry}
            dup = used.intersection(declared)
            if dup or len(set(declared)) != len(declared):
                raise ValueError(
                    f"repartition(axes={declared}): axes already sharding "
                    f"this tensor ({sorted(used)})")
            a[params.dim].extend(declared)
        else:
            used = {ax for entry in a for ax in entry}
            for name, size in mesh.shape.items():
                if size == params.degree and name not in used:
                    a[params.dim].append(name)
                    break
            else:
                raise ValueError(
                    f"repartition(degree={params.degree}): no unused mesh "
                    f"axis of that size in {dict(mesh.shape)}"
                )
    elif op_type == OT.OP_COMBINE:
        if declared and a[params.dim][-len(declared):] == list(declared):
            del a[params.dim][-len(declared):]
        else:
            removed = 1
            while removed < params.degree and a[params.dim]:
                removed *= mesh.shape[a[params.dim].pop()]
            if removed != params.degree:
                raise ValueError(
                    f"combine(degree={params.degree}) cannot unshard "
                    f"assignment {in_assignment[params.dim]} over "
                    f"{dict(mesh.shape)}"
                )
    elif op_type == OT.OP_FUSED_PARALLEL:
        cur = tuple(tuple(x) for x in a)
        for info in params.ops:
            sub = _INFO_PARAMS.get(info.op_type)
            if sub is not None:
                cur = derive_parallel_assignment(
                    info.op_type, sub(info), cur, mesh
                )
        return cur
    # Replicate / Reduction / Pipeline: replication and partial-sum state are
    # implicit under GSPMD; the assignment passes through unchanged.
    return tuple(tuple(x) for x in a)
