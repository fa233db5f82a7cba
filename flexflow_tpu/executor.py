"""Executor: lowers a PCG to one jitted SPMD training/eval step.

This replaces the reference's entire L0-L2 stack (Legion index tasks + FFMapper
+ per-op CUDA kernels, SURVEY §1): the topo-ordered PCG becomes a single pure
function traced under `jax.jit`; each node's searched placement is pinned with
`with_sharding_constraint` (the GSPMD analog of tagging region requirements
with `machine_view.hash()`, src/ops/linear.cc:352-359), so the plan the search
chose is the plan XLA runs, and re-sharding between differently-placed ops is
compiled into ICI collectives exactly where the reference would launch
parallel-op copy tasks.

Autodiff (`jax.value_and_grad`) replaces all hand-written backward tasks;
Legion tracing (`begin_trace/end_trace` around each iteration) is subsumed by
the jit compilation cache; the optimizer update runs sharded in the same
program, so the whole training iteration is one XLA executable — the same
"single traced hot loop" property the reference gets from Legion trace replay.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .config import FFConfig
from .fftype import CompMode, DataType, LossType, OperatorType as OT, dtype_to_jnp
from .initializer import initializer_by_name
from .loss import loss_terms
from .metrics import Metrics
from .ops.base import OpContext
from .optimizer import Optimizer
from .pcg.graph import Graph, OpNode


def _stable_fold(key, name: str):
    h = int.from_bytes(hashlib.md5(name.encode()).digest()[:4], "little")
    return jax.random.fold_in(key, h)


class Executor:
    def __init__(
        self,
        graph: Graph,
        mesh: Mesh,
        config: FFConfig,
        loss_type: LossType,
        metrics: Metrics,
        optimizer: Optimizer,
        logits_node: OpNode,
        label_spec: PartitionSpec,
        update_sharding: Optional[dict] = None,
    ):
        self.graph = graph
        self.mesh = mesh
        self.config = config
        self.loss_type = loss_type
        self.metrics = metrics
        self.optimizer = optimizer
        self.order = graph.topo_order()
        self.logits_node = logits_node
        self.label_spec = label_spec
        # weight-update sharding (ZeRO / Xu et al.; decided by
        # unity.choose_update_sharding): fp32 masters + optimizer slots of
        # each shardable trainable weight live 1/dp-sharded along its
        # gradient-reduction axes. update_specs[(node, weight)] = (spec,
        # shape): the at-rest PartitionSpec init_variables places with and
        # the train step pins grads / updated params / slots to — GSPMD
        # then lowers the grad psum into a reduce-scatter in layer order
        # and defers the updated-param all-gather into each consumer's
        # first use next step (it fuses with the _cast_compute downcast at
        # that seam). The update math is element-wise on the same reduced
        # gradient values, so the trajectory is bit-identical to the
        # replicated update.
        self.update_sharding = update_sharding or {"enabled": False}
        self.update_specs: dict[tuple[str, str], tuple] = {}
        # ZeRO-3 / FSDP stage 3 (choose_update_sharding stage == 3): the
        # trainable weights themselves live sharded at rest in the SAME
        # update_specs layout, and _apply brings each layer's params to
        # their compute placement where the layer uses them, by XLA's
        # all-gather (parallel/ops.all_gather), once a step: the
        # backward reads the forward's gathered copy. gather_specs
        # holds, per sharded weight, what the gather needs: the compute
        # placement it restores, the update axes it unwinds, and the dim
        # they shard. gather_schedule is the order the layers' gathers
        # enter the step in, from the PCG topological order: (owner, the
        # owner gathered before it).
        self.update_stage = int(self.update_sharding.get(
            "stage", 2 if self.update_sharding.get("enabled") else 0))
        self.gather_specs: dict[tuple[str, str], tuple] = {}
        self.gather_schedule: list[tuple[str, Optional[str]]] = []
        # custom-VJP gather callables keyed by (owner, wname); built once
        # per weight at first trace
        self._gather_fns: dict[tuple[str, str], Any] = {}
        if self.update_sharding.get("enabled"):
            self._build_update_specs()
        # At-rest placement of every weight, trainable or state: where
        # init_variables puts it and where every step leaves it —
        # rest_specs[(node, weight)] = (spec, shape), the update layout
        # where the weight update is sharded, else the plan's weight
        # placement. The steps pin their outputs to it: left to GSPMD, an
        # updated weight can come back in the layout of its gradient (a
        # LayerNorm scale under a feature-sharded output, say), and the
        # next call then meets arguments in a layout it was not compiled
        # for — a second compilation of the whole step, state the donated
        # buffers cannot alias, and a layout the plan never priced.
        self.rest_specs: dict[tuple[str, str], tuple] = {}
        for node in self.order:
            if getattr(node, "weight_source", None):
                continue  # tied weights live under the source node's name
            for ws in node.weight_specs:
                upd = self.update_specs.get((node.name, ws.name))
                spec = (upd[0] if upd is not None else
                        node.weight_axes.get(ws.name, PartitionSpec()))
                # without trailing Nones, the form a step's outputs come
                # back in: P('data', None) and P('data') place alike but
                # do not compare equal, and a second call whose arguments
                # compare unequal to the first's is dispatched from
                # scratch (≈2 s for lm-base on four chips)
                entries = list(spec)
                while entries and entries[-1] is None:
                    entries.pop()
                self.rest_specs[(node.name, ws.name)] = (
                    PartitionSpec(*entries), tuple(ws.shape))
        # A substitution rewrite may have interposed Combine/Repartition/...
        # nodes between the real softmax and the marked logits node; walk
        # back through value-preserving parallel ops so the loss doesn't
        # re-apply log-softmax to probabilities after such a rewrite.
        terminal = _terminal_compute_op(graph, logits_node)
        self.last_op_is_softmax = terminal.op_type == OT.OP_SOFTMAX
        # AggregateSpec emits per-token-copy rows (k*b, dim) in copy-major
        # order; labels must be replicated k× to score every expert's
        # prediction (the reference replicates the label tensor at compile
        # when the final op is OP_AGG_SPEC, model.cc:2875). A trailing
        # softmax doesn't change the row count — look through it.
        self.label_replication = 1
        spec_probe = terminal
        if spec_probe.op_type == OT.OP_SOFTMAX:
            edges = graph.in_edges[spec_probe.guid]
            if edges:
                e = sorted(edges, key=lambda e: e.dst_idx)[0]
                spec_probe = _terminal_compute_op(graph, graph.nodes[e.src])
        if spec_probe.op_type == OT.OP_AGG_SPEC and spec_probe.inputs:
            self.label_replication = (
                spec_probe.inputs[0].shape.logical_shape[1])
        # Mixed precision (config.py): compute_dtype != None → bf16/fp16
        # activations with fp32 master weights; matmul_dtype → MXU input cast
        # for fp32 matmuls (tensor-op math analog).
        self.compute_dtype = (
            dtype_to_jnp(config.computation_dtype)
            if config.computation_dtype is not None else None
        )
        self.matmul_dtype = (
            jnp.bfloat16
            if config.allow_tensor_op_math_conversion
            and (jax.default_backend() == "tpu" or config.force_tensor_op_math)
            else None
        )
        # At-rest dtype of every weight, trainable or state: what
        # init_variables allocates and what every step leaves —
        # rest_dtypes[(node, weight)], the WeightSpec's declared dtype.
        # One exception: an inference compile (a serving decode graph)
        # under a compute dtype holds its floating parameters in that
        # dtype. Nothing updates them, so fp32 masters would only be
        # re-read and re-cast by every step; adopt_params casts each
        # trained weight once on the way in and _cast_compute at first
        # use then finds nothing to do. A training compile keeps fp32
        # masters.
        narrow_params = (
            self.compute_dtype is not None
            and config.computation_mode == CompMode.COMP_MODE_INFERENCE)
        self.rest_dtypes: dict[tuple[str, str], Any] = {}
        for node in self.order:
            if getattr(node, "weight_source", None):
                continue
            for ws in node.weight_specs:
                dt = dtype_to_jnp(ws.dtype)
                if (narrow_params and ws.trainable
                        and jnp.issubdtype(dt, jnp.floating)):
                    dt = self.compute_dtype
                self.rest_dtypes[(node.name, ws.name)] = dt
        self._train_step = None
        self._eval_step = None
        self._forward_fn = None
        self._decode_step = None  # serving decode executable (serving/)
        # the graph's row-wise tail, found once (decode_tail), and what
        # the graph was made to serve (a DecodeContext: its slots, its
        # max_seq), where serving/decode_graph.py made it
        self._decode_tail: Optional[tuple] = None
        self.decode_context = None
        # chunked (lax.scan) train steps keyed by chunk length — the
        # pipelined engine's fused multi-step dispatch (engine/)
        self._chunk_steps: dict[int, Any] = {}
        # ffsan runtime sanitizer (--sanitize-numerics, sanitize.py):
        # when on, _apply wraps every op output in finiteness probes
        # (fwd value + bwd cotangent) that localize the first non-finite
        # tensor to (op, phase, step). Off → no probes traced, the step
        # is byte-identical to the uninstrumented one.
        self.sanitize_numerics = bool(
            getattr(config, "sanitize_numerics", False))
        # test/debug fault injection: (op_name | "loss", "fwd"|"bwd",
        # step) — poisons exactly that tensor from that step on
        self._numeric_fault: Optional[tuple] = None

    def _build_update_specs(self):
        """Resolve the per-weight update shardings through the SAME
        helpers the cost model prices with (parallel/ops): for every
        trainable, non-tied weight, the gradient-reduction axes (consumer
        activation axes minus the weight's own) extend the plan's compute
        spec on the first divisible dim. Non-shardable weights stay
        replicated — their update is the replicated baseline (still
        bit-identical). Emits the weight_update telemetry event plus one
        grad_sync bytes counter per layer-order bucket (= param-owning
        node) so the drift monitor sees the new comm channel."""
        from . import telemetry
        from .parallel.ops import (
            _spec_assignment, choose_update_dim, grad_sync_axes,
            weight_update_spec,
        )

        axis_sizes = {k: int(v) for k, v in dict(self.mesh.shape).items()}
        total_bytes = 0
        buckets = 0
        used_axes: set = set()
        max_shards = 1
        for node in self.order:
            if getattr(node, "weight_source", None):
                continue
            out_axes = set()
            if node.outputs:
                for entry in node.outputs[0].partition_spec():
                    if entry is None:
                        continue
                    out_axes.update(entry if isinstance(entry, tuple)
                                    else (entry,))
            bucket_bytes = 0
            for ws in node.weight_specs:
                if not ws.trainable:
                    continue
                base = node.weight_axes.get(ws.name, PartitionSpec())
                w_axes = set()
                for entry in base:
                    if entry is None:
                        continue
                    w_axes.update(entry if isinstance(entry, tuple)
                                  else (entry,))
                axes = tuple(ax for ax in grad_sync_axes(out_axes, w_axes)
                             if axis_sizes.get(ax, 1) > 1)
                if not axes:
                    continue
                spec = weight_update_spec(ws.shape, base, axes, axis_sizes)
                if spec is None:
                    continue
                self.update_specs[(node.name, ws.name)] = (
                    spec, tuple(ws.shape))
                if self.update_stage >= 3:
                    # stage 3: record what the just-in-time gather needs
                    # — the compute placement it restores (base), the
                    # update axes it unwinds, and the dim they shard
                    dim = choose_update_dim(
                        ws.shape, _spec_assignment(base, len(ws.shape)),
                        axes, axis_sizes)
                    self.gather_specs[(node.name, ws.name)] = (
                        base, spec, tuple(axes), dim)
                used_axes.update(axes)
                deg = 1
                for ax in axes:
                    deg *= axis_sizes.get(ax, 1)
                max_shards = max(max_shards, deg)
                nbytes = int(np.prod(ws.shape)) * 4
                bucket_bytes += nbytes
                total_bytes += nbytes
            if bucket_bytes:
                buckets += 1
                telemetry.counter("grad_sync", {
                    "bucket": buckets, "bytes": bucket_bytes})
        self.update_sharding = dict(self.update_sharding,
                                    buckets=buckets,
                                    sharded_weights=len(self.update_specs),
                                    bytes=total_bytes)
        if self.gather_specs:
            # the order the layers' gathers enter the step in, from the
            # PCG topological order: entry k names the owner gathered
            # before it (None for the first)
            owners = []
            for node in self.order:
                if getattr(node, "weight_source", None):
                    continue
                if any((node.name, ws.name) in self.gather_specs
                       for ws in node.weight_specs):
                    owners.append(node.name)
            self.gather_schedule = [
                (name, owners[i - 1] if i > 0 else None)
                for i, name in enumerate(owners)]
            # what a step's gathers deliver to a chip's compute
            # placement, in the dtype the wire carries (XLA hoists the
            # compute-dtype cast in front of the collective), and how
            # many collectives the step's graph holds: one an update
            # axis a weight, the forward's only
            cd = self.config.computation_dtype
            itemsize = jnp.dtype(dtype_to_jnp(cd)).itemsize if cd else 4
            telemetry.event(
                "param_gather",
                layers=len(owners),
                sharded_weights=len(self.gather_specs),
                bytes=sum(int(np.prod(self.update_specs[key][1])) * itemsize
                          for key in self.gather_specs),
                collective="all-gather",
                gathers_per_step=sum(
                    len(axes) for _b, _u, axes, _d in
                    self.gather_specs.values()))
        if self.update_specs:
            # the REALIZED layout can exceed the decision's dp-default
            # guess (a seq-sharded consumer adds `seq` to a weight's
            # reduction axes): record what actually runs — the manifest,
            # the weight_update event, and strategy_report all read this
            self.update_sharding["axes"] = sorted(used_axes)
            self.update_sharding["shards"] = max_shards
        else:
            # decided (or forced) sharded but no weight had a divisible
            # dim: nothing runs sharded, so the record — and everything
            # downstream that prices or audits it — must say replicated
            self.update_sharding.update(
                enabled=False, stage=0, shards=1, axes=[],
                reason=self.update_sharding.get("reason", "")
                + "+no_shardable_weight")
            self.update_stage = 0
            self.gather_specs.clear()
        if self.update_specs:
            telemetry.event(
                "weight_update",
                stage=self.update_stage,
                shards=int(self.update_sharding.get("shards", 1)),
                buckets=buckets, sharded_weights=len(self.update_specs),
                bytes=total_bytes)

    def _map_leaves(self, tree, fn, specs: dict):
        """Apply `fn(leaf, NamedSharding)` to every leaf `specs` names.
        Leaves are matched by the (node, weight) tail of their tree path —
        the same two keys for params/grads/state ({node: {w}}) and slot
        trees ({m: {node: {w}}}) — and only when the leaf has the
        weight's full shape (SGD's momentum-off scalar slots pass
        through)."""
        if not specs:
            return tree
        import jax.tree_util as jtu

        flat, treedef = jtu.tree_flatten_with_path(tree)
        out = []
        for path, leaf in flat:
            keys = tuple(k.key for k in path if isinstance(k, jtu.DictKey))
            entry = specs.get(keys[-2:]) if len(keys) >= 2 else None
            if entry is not None and tuple(
                    getattr(leaf, "shape", ())) == entry[1]:
                leaf = fn(leaf, NamedSharding(self.mesh, entry[0]))
            out.append(leaf)
        return jtu.tree_unflatten(treedef, out)

    def _pin_update_sharding(self, tree):
        """Constrain gradients to their update shardings inside the
        jitted step (no-op when the update is not sharded)."""
        return self._map_leaves(
            tree, jax.lax.with_sharding_constraint, self.update_specs)

    def _pin_at_rest(self, tree):
        """Constrain a step's outgoing params / optimizer slots / state
        to their at-rest placement (rest_specs). Nothing to pin on one
        device."""
        if self.mesh.size == 1:
            return tree
        return self._map_leaves(
            tree, jax.lax.with_sharding_constraint, self.rest_specs)

    def place_update_sharded(self, tree):
        """device_put leaves onto their at-rest shardings (outside jit) —
        compile-time placement of optimizer slots built by zeros_like, and
        insurance that params/slots restored or constructed elsewhere land
        at rest in the (update-)sharded layout."""
        return self._map_leaves(tree, jax.device_put, self.rest_specs)

    # -------------------------------------------------- stage-3 gathers

    def _gather_param(self, owner: str, wname: str, arr):
        """All-gather one stage-3 weight from its at-rest update layout
        back to its compute placement — exact data movement, so the
        gathered value is bit-identical to a replicated weight. XLA's
        own collective, one an update axis; multi-axis updates unwind
        minor axis first (weight_update_spec appends the update axes
        onto the dim, so chunks concatenate in shard order within each
        outer shard). XLA runs the matrices' gathers asynchronously
        beside the work that precedes their reader (the compiled text
        threads each through the fusions before it) and the vectors' at
        their reader; what the chip measured is in PERF.md (section 6,
        PR 48)."""
        from .parallel.ops import _spec_assignment, all_gather

        base, upd, axes, dim = self.gather_specs[(owner, wname)]
        cur = list(_spec_assignment(upd, arr.ndim))

        def to_spec(assignment):
            return PartitionSpec(*(
                None if not e else (e[0] if len(e) == 1 else tuple(e))
                for e in assignment))

        with jax.named_scope(f"param_gather/{owner}.{wname}"):
            for ax in reversed(axes):
                nxt = list(cur)
                entry = list(nxt[dim])
                entry.remove(ax)
                nxt[dim] = tuple(entry)
                arr = all_gather(
                    arr, mesh=self.mesh, axis_name=ax, dim=dim,
                    in_spec=to_spec(cur), out_spec=to_spec(nxt))
                cur = nxt
        return arr

    def _gather_with_vjp(self, owner: str, wname: str):
        """The stage-3 gather as a custom-VJP callable (built once per
        weight): forward = the all-gather of _gather_param; backward = the
        gathered copy's cotangent pinned to the compute placement
        (replicated over the update axes) — the exact stage-2 gradient
        path, so GSPMD lowers the dp psum into the same reduce-scatter
        and the trajectory stays bit-identical to the replicated
        baseline; _pin_update_sharding then slices the owner's shard.
        (Autodiff THROUGH the gather would reduce-scatter the cotangent
        in the collective's own order, which need not be the allreduce's
        ULP order; a ring of hops measured ~1e-7 drift on the CI mesh.)"""
        key = (owner, wname)
        fn = self._gather_fns.get(key)
        if fn is not None:
            return fn
        base = self.gather_specs[key][0]
        base_sh = NamedSharding(
            self.mesh, base if base is not None else PartitionSpec())

        @jax.custom_vjp
        def gather(w):
            return self._gather_param(owner, wname, w)

        def fwd(w):
            return gather(w), None

        def bwd(_, ct):
            return (jax.lax.with_sharding_constraint(ct, base_sh),)

        gather.defvjp(fwd, bwd)
        self._gather_fns[key] = gather
        return gather

    def _cast_compute(self, tree):
        """Cast float leaves to the compute dtype (inside jit; the VJP of the
        cast accumulates gradients back into the fp32 master leaves)."""
        cd = self.compute_dtype
        if cd is None:
            return tree
        return jax.tree.map(
            lambda x: x.astype(cd)
            if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x,
            tree,
        )

    def set_numeric_fault(self, op: Optional[str], phase: str = "fwd",
                          step: int = 0):
        """Install (or clear, op=None) a numeric fault: the named op's
        output (or its cotangent, phase="bwd"; op "loss" targets the
        scalar loss) goes NaN from global step `step` on. Test/debug
        hook for the sanitizer's localization matrix — the cached step
        executables are dropped so the next dispatch retraces with the
        fault baked in."""
        if op is not None:
            if phase not in ("fwd", "bwd"):
                raise ValueError(f"phase must be fwd|bwd, got {phase!r}")
            if op != "loss" and all(n.name != op for n in self.order):
                raise ValueError(f"no op named {op!r} in the graph")
        self._numeric_fault = (
            None if op is None else (op, phase, int(step)))
        self._train_step = None
        self._eval_step = None
        self._forward_fn = None
        self._decode_step = None
        self._chunk_steps.clear()

    def _maybe_poison(self, x, name: str, step, phase: str):
        """Apply the installed numeric fault to tensor `name`, for the
        given phase only. Wrap order vs the sanitizer probe matters: a
        fwd fault is applied BEFORE the probe (so the probe sees the
        poisoned value), a bwd fault AFTER it (so the probe's backward
        sees the poisoned cotangent — bwd composition reverses the
        forward wrap order)."""
        fault = self._numeric_fault
        if fault is None or fault[0] != name or fault[1] != phase:
            return x
        from . import sanitize

        _op, _phase, at = fault
        if phase == "fwd":
            return sanitize.inject_nonfinite(x, step, at)
        return sanitize.inject_grad_nonfinite(
            x, step if step is not None else jnp.int32(-1), at)

    def make_loss_fn(self, state, x_inputs, labels, rng, step=None):
        """Shared mixed-precision loss closure for the fused train step and
        the granular FFModel.backward: bf16 compute casts on params/inputs
        (state is passed uncast — ops own their fp32-statistics handling).
        Params are passed UNCAST into `_apply`, which casts each node's
        weights at their first use — the cast fuses into the consumer's
        matmul prologue instead of materializing a full bf16 parameter
        copy through HBM every step (PERF.md "remaining headroom": the
        per-step fp32-master downcast traffic). The VJP is unchanged (a
        per-leaf astype either way), so gradients still accumulate into
        the fp32 masters bit-identically.
        Logits stay in the compute dtype — the loss reduces them with f32
        accumulation internally (loss.py), so no logits-sized f32 tensor is
        materialized. aux carries (logits, new_state, ce_sum): ce_sum is the
        reusable sparse-CE sum for Metrics (None for non-SCCE losses)."""
        xc = self._cast_compute(x_inputs)
        labels = self.expand_labels(labels)

        def loss_fn(p):
            logits, new_state, aux = self._apply(
                p, state, xc, training=True, rng=rng, step=step
            )
            l, ce_sum = loss_terms(
                self.loss_type, logits, labels, self.last_op_is_softmax
            )
            total = l + aux
            total = self._maybe_poison(total, "loss", step, "fwd")
            if self.sanitize_numerics:
                from . import sanitize

                # the loss sits one past the last graph op in topo space
                total = sanitize.probe(total, step, "loss",
                                       len(self.order))
            total = self._maybe_poison(total, "loss", step, "bwd")
            return total, (logits, new_state, ce_sum)

        return loss_fn

    def expand_labels(self, labels):
        """Replicate labels k× for an AggregateSpec terminal (copy-major,
        matching _agg_spec_forward's (k*b, dim) row order) — the
        model.cc:2875 label replication."""
        k = self.label_replication
        if k <= 1:
            return labels
        reps = (k,) + (1,) * (labels.ndim - 1)
        return jnp.tile(labels, reps)

    def _restore_state_dtypes(self, new_state):
        """Non-trainable state leaves a step in the dtype it was declared
        in (rest_dtypes: fp32 for running statistics, the compute dtype
        for a KV cache a decode graph declared so; fp32 for a floating
        leaf an op hands back undeclared), so its dtype — and therefore
        the jitted step signature — is stable."""
        if self.compute_dtype is None:
            return new_state

        def restore(node, wname, leaf):
            dt = self.rest_dtypes.get((node, wname), jnp.float32)
            return jax.tree.map(
                lambda x: x.astype(dt)
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x,
                leaf)

        return {node: {w: restore(node, w, leaf) for w, leaf in ws.items()}
                for node, ws in new_state.items()}

    # ------------------------------------------------------------ variables

    def init_variables(self, rng, shared=None):
        """Initialize params (trainable) and state (non-trainable weights),
        each placed with its searched sharding (replaces weight-region mapping
        in model.cc map_weight + initializer tasks). `shared`: {(node,
        weight): array} of another model's parameters; one that has this
        weight's shape, dtype and placement is taken as it is instead of
        a fresh one (a decode graph over an inference compile: the
        device holds the weights once)."""
        params, state = {}, {}
        shared = shared or {}
        for node in self.order:
            if getattr(node, "weight_source", None):
                continue  # tied weights live under the source node's name
            p, s = {}, {}
            for i, ws in enumerate(node.weight_specs):
                init = node.initializers.get(
                    ws.name, initializer_by_name(ws.initializer)
                )
                spec = self.rest_specs[(node.name, ws.name)][0]
                dtype = self.rest_dtypes[(node.name, ws.name)]
                have = shared.get((node.name, ws.name))
                if (have is not None and ws.trainable
                        and have.shape == tuple(ws.shape)
                        and have.dtype == dtype
                        and have.sharding.is_equivalent_to(
                            NamedSharding(self.mesh, spec), have.ndim)):
                    p[ws.name] = have
                    continue
                key = _stable_fold(rng, f"{node.name}/{ws.name}")
                arr = init(key, ws.shape, dtype)
                # at-rest layout. Under weight-update sharding the fp32
                # master lives 1/dp-sharded — stage 2: consumers
                # all-gather at first use (GSPMD, fused with their
                # compute-dtype cast); stage 3: _apply all-gathers each
                # layer's weights where it uses them, once a step.
                arr = jax.device_put(arr, NamedSharding(self.mesh, spec))
                (p if ws.trainable else s)[ws.name] = arr
            if p:
                params[node.name] = p
            if s:
                state[node.name] = s
        return params, state

    # ------------------------------------------------------------ apply

    def _apply(self, params, state, inputs, *, training, rng,
               seq_length=-1, step=None, upto_tail: bool = False):
        """Run the PCG forward. Returns (logits, new_state, aux_loss).
        `step` (traced int or None) feeds the sanitizer probes and the
        fault injector so localization carries the exact step inside
        chunked lax.scan dispatches too. `upto_tail` stops in front of
        the graph's row-wise tail (`decode_tail`) and returns the tail's
        input in the logits' place: `_apply_tail` then runs the tail on
        rows of it."""
        tail_nodes, cut = (self.decode_tail() if upto_tail
                           else ((), (self.logits_node.guid, 0)))
        skip = {n.guid for n in tail_nodes}
        vals: dict[tuple[int, int], Any] = {}
        new_state = {k: dict(v) for k, v in state.items()}
        aux_loss = 0.0
        for topo_idx, node in enumerate(self.order):
            if node.guid in skip:
                continue
            if node.op_type in (OT.OP_INPUT, OT.OP_WEIGHT, OT.OP_NOOP):
                if node.op_type == OT.OP_INPUT:
                    vals[(node.guid, 0)] = self._placed(
                        inputs[node.name], node.outputs[0].partition_spec())
                elif self.graph.in_edges[node.guid]:
                    src, sidx = self.graph.producer(node, 0)
                    vals[(node.guid, 0)] = vals[(src.guid, sidx)]
                continue

            ins = [None] * len(self.graph.in_edges[node.guid])
            for e in self.graph.in_edges[node.guid]:
                ins[e.dst_idx] = vals[(e.src, e.src_idx)]
            outs, aux = self._run_node(
                node, topo_idx, ins, params, new_state, training=training,
                rng=rng, seq_length=seq_length, step=step)
            if aux is not None:
                aux_loss = aux_loss + aux
            for i, out in enumerate(outs):
                vals[(node.guid, i)] = out

        return vals[cut], new_state, aux_loss

    def _apply_tail(self, params, rows):
        """The graph's row-wise tail (`decode_tail`) on `rows`, some rows
        of what `_apply(upto_tail=True)` returned, one in the place of a
        declared row or more: the logits of those rows. The tail keeps
        no state and a serving step runs it outside training."""
        for node in self.decode_tail()[0]:
            (rows, *_), _ = self._run_node(
                node, self.order.index(node), [rows], params, {},
                training=False, rng=None, seq_length=-1, step=None,
                any_rows=True)
        return rows

    def decode_tail(self) -> tuple:
        """(the nodes of the graph's row-wise tail in the order they run,
        the (guid, output) their first reads): back from the logits node
        through nodes that read one value, are read by the next alone,
        keep nothing from call to call and act on each row by itself
        (`OpDef.row_wise`), so that the tail of some rows is those rows
        of the tail. `ln_f` -> `lm_head` of an LM's trunk; () and the
        logits themselves where the last op is not row-wise."""
        if self._decode_tail is None:
            graph, node, sidx, tail = self.graph, self.logits_node, 0, []
            while (node.op_type not in (OT.OP_INPUT, OT.OP_WEIGHT,
                                        OT.OP_NOOP)
                   and len(graph.in_edges[node.guid]) == 1
                   and len(graph.out_edges[node.guid]) == (1 if tail else 0)
                   and all(ws.trainable for ws in node.weight_specs)
                   and node.op_def.row_wise(
                       node.params,
                       [t.shape.logical_shape for t in node.inputs])):
                tail.append(node)
                node, sidx = graph.producer(node, 0)
            self._decode_tail = (tuple(reversed(tail)), (node.guid, sidx))
        return self._decode_tail

    def _placed(self, x, spec: PartitionSpec, any_rows: bool = False):
        """`x` pinned to the plan's placement of it; with `any_rows`, a
        number of rows the axes of dim 0 do not divide stays whole."""
        if not _spec_nontrivial(spec):
            return x
        if any_rows and spec[0] is not None:
            axes = spec[0] if isinstance(spec[0], tuple) else (spec[0],)
            if x.shape[0] % int(np.prod([self.mesh.shape[a]
                                         for a in axes])):
                spec = PartitionSpec(None, *spec[1:])
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def _run_node(self, node, topo_idx, ins, params, new_state, *,
                  training, rng, seq_length, step, any_rows=False):
        """One compute node of the forward: (its outputs as the plan
        places them, its auxiliary loss or None); what it keeps from call
        to call goes into `new_state`."""
        if self.sanitize_numerics:
            from . import sanitize
        # tied weights read the source node's parameter set; autodiff
        # then sums every use's gradient into that one set
        wsrc = getattr(node, "weight_source", None) or node.name
        p_own = params.get(wsrc, {})
        ctx = OpContext(
            training=training,
            rng=_stable_fold(rng, node.name) if rng is not None else None,
            seq_length=seq_length,
            profiling=self.config.profiling,
            mesh=self.mesh,
            out_spec=(node.outputs[0].partition_spec()
                      if node.outputs else None),
            weight_axes=node.weight_axes,
            matmul_dtype=self.matmul_dtype,
            overlap_collectives=self.config.overlap_collectives,
        )
        op_state = new_state.get(node.name)
        # named_scope labels the op in XLA profiles (the analog of the
        # reference's per-op profiling prints, linear_kernels.cu:95-117)
        with jax.named_scope(node.name):
            if self.gather_specs:
                # stage 3 (ZeRO-3/FSDP): the weights that rest sharded
                # over the update axes come to their compute placement
                # here, once a step; the backward reads the same
                # gathered value, in the compute dtype
                p_own = {k: (self._gather_with_vjp(wsrc, k)(v)
                             if (wsrc, k) in self.gather_specs else v)
                         for k, v in p_own.items()}
            weights = {}
            # bf16 cast at the consumer: each node casts only its
            # own weights, so XLA fuses the downcast into the
            # first use instead of writing a model-sized bf16
            # copy to HBM up front (state stays uncast — ops own
            # their fp32-statistics handling). An inference
            # compile's parameters rest in the compute dtype
            # (rest_dtypes): nothing is cast
            weights.update(self._cast_compute(p_own))
            weights.update(new_state.get(wsrc, {}))
            outs, op_state = node.op_def.forward(
                node.params, ins, weights, op_state, ctx
            )
        aux = None
        if op_state:
            op_state = dict(op_state)
            aux = op_state.pop("aux_loss", None)
            if op_state:
                cur = new_state.setdefault(node.name, {})
                cur.update(op_state)

        placed = []
        for i, out in enumerate(outs):
            if i < len(node.outputs):
                out = self._placed(out, node.outputs[i].partition_spec(),
                                   any_rows)
            if i == 0 and self._numeric_fault is not None:
                out = self._maybe_poison(out, node.name, step, "fwd")
            if self.sanitize_numerics:
                label = (node.name if i == 0
                         else f"{node.name}#out{i}")
                out = sanitize.probe(out, step, label, topo_idx)
            if i == 0 and self._numeric_fault is not None:
                out = self._maybe_poison(out, node.name, step, "bwd")
            placed.append(out)
        return placed, aux

    # ------------------------------------------------------------ steps

    def _train_step_body(self, params, state, opt_slots, step, counters,
                         rng, batch):
        """One iteration's math: fwd + loss + bwd + optimizer + metrics.
        Shared verbatim between the eager per-step jit and the chunked
        lax.scan body, so the pipelined engine is bit-identical to the
        eager loop by construction."""
        x_inputs, labels = batch
        loss_fn = self.make_loss_fn(state, x_inputs, labels, rng,
                                    step=step)
        (lval, (logits, new_state, ce_sum)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        new_state = self._restore_state_dtypes(new_state)
        if self.update_specs:
            # sharded weight update (ZeRO / Xu et al.): pin each bucket's
            # gradient to the 1/dp update layout, so GSPMD lowers the dp
            # psum into a reduce-scatter per layer-order bucket — the hop
            # for bucket k free to overlap the backward compute producing
            # bucket k+1 (no data dependence between them; the same
            # latency-hiding the ring bodies exploit). The sharded update
            # below then touches only this replica's shard; the updated
            # params stay sharded at rest and each consumer's first use
            # next step all-gathers them, fused with its compute cast.
            # Bit-identical: the same reduced gradient elements feed the
            # same element-wise update — each replica just owns a slice.
            # (The span fires at trace time — one per compile, labelling
            # the executable that carries the RS/AG schedule.)
            from . import telemetry

            with telemetry.span(
                    "grad_sync",
                    shards=int(self.update_sharding.get("shards", 1)),
                    buckets=int(self.update_sharding.get("buckets", 0))):
                with jax.named_scope("grad_sync"):
                    grads = self._pin_update_sharding(grads)
        # named for ffscope attribution: optimizer math that belongs to
        # no single PCG node lands in the profile section's extras map
        with jax.named_scope("weight_update"):
            new_params, new_slots = self.optimizer.update(
                grads, params, opt_slots, step
            )
        with jax.named_scope("weight_update_shard"):
            new_params = self._pin_at_rest(new_params)
            new_slots = self._pin_at_rest(new_slots)
            new_state = self._pin_at_rest(new_state)
        with jax.named_scope("metrics"):
            counters = self.metrics.compute(
                counters, logits, self.expand_labels(labels),
                from_logits=not self.last_op_is_softmax, scce_sum=ce_sum,
            )
        return new_params, new_state, new_slots, step + 1, counters, lval

    def build_train_step(self):
        """One fused iteration: fwd + loss + bwd + optimizer + metrics.
        Mirrors the traced loop of FFModel::fit (flexflow_cffi.py:2058-2100)
        collapsed into a single XLA executable."""
        self._train_step = jax.jit(
            self._train_step_body,
            donate_argnums=_donate_argnums((0, 1, 2, 3, 4)))
        return self._train_step

    def build_chunked_train_step(self, num_steps: int):
        """`num_steps` train iterations fused into ONE donated executable:
        a lax.scan over pre-staged batches (leading scan axis) and
        pre-split per-step RNG keys, carrying the full training state and
        emitting the per-step loss vector — the TPU-native analog of the
        reference's Legion trace replay batching N iterations per runtime
        round-trip. Cached per chunk length (an epoch tail shorter than
        the pipeline depth costs one extra compile, once)."""
        num_steps = int(num_steps)
        if num_steps < 1:
            raise ValueError(f"num_steps must be >= 1, got {num_steps}")
        cached = self._chunk_steps.get(num_steps)
        if cached is not None:
            return cached

        def chunk_step(params, state, opt_slots, step, counters, rngs,
                       batches):
            def body(carry, inp):
                rng, batch = inp
                out = self._train_step_body(*carry, rng, batch)
                return tuple(out[:5]), out[5]

            carry, losses = jax.lax.scan(
                body, (params, state, opt_slots, step, counters),
                (rngs, batches), length=num_steps)
            return carry + (losses,)

        fn = jax.jit(chunk_step,
                     donate_argnums=_donate_argnums((0, 1, 2, 3, 4)))
        self._chunk_steps[num_steps] = fn
        return fn

    def build_eval_step(self):
        def eval_step(params, state, counters, batch):
            x_inputs, labels = batch
            logits, _, _ = self._apply(
                params, state,
                self._cast_compute(x_inputs), training=False, rng=None,
            )
            counters = self.metrics.compute(
                counters, logits, self.expand_labels(labels),
                from_logits=not self.last_op_is_softmax,
            )
            return counters

        self._eval_step = jax.jit(eval_step, donate_argnums=_donate_argnums((2,)))
        return self._eval_step

    def build_decode_step(self):
        """ONE serving iteration as a donated executable: forward the
        decode graph (incremental attention reads+writes the KV-cache
        state threaded through `state`) up to its row-wise tail
        (`decode_tail`: the final norm and the vocabulary head), then
        run the tail on the rows a token is read from and sample from
        them — argmax where `temperature[row] == 0`, Gumbel sampling
        otherwise, in the same program so only the (rows,) token vector
        crosses the host boundary. Which rows those are follows from the
        call's shape beside the slots the graph was made for
        (`decode_context`; a graph made otherwise: every row a slot's):

          - (slots, 1), a step that only decodes: every row, nothing
            gathered in front of the tail;
          - (slots, q), the rectangle: row `read_idx[slot]` of each slot;
          - (slots + bucket, 1), a chunk as rows past the slots': the
            slots' rows and ONE more, the chunk's last live row, found
            from the call's own `positions` (a dead row carries the
            scratch position, the graph's max_seq). `sampled` has the
            drawn token at those rows and 0 at the others.

        Donating `state` updates the cache in place on backends that
        support donation (the TPU serving hot loop allocates nothing
        per token). Distinct q_len values (decode=1, prefill buckets)
        retrace into their own cached executables — the length-bucketed
        executable set falls out of jit's shape specialization."""
        served = self.decode_context

        def decode_step(params, state, x_inputs, read_idx, rng, temperature):
            hidden, new_state, _ = self._apply(
                params, state,
                self._cast_compute(x_inputs), training=False, rng=None,
                upto_tail=True,
            )
            rows, q = hidden.shape[:2]
            slots = served.slots if served is not None else rows
            last = None  # the chunk's last live row, where it rides as rows
            if rows > slots:
                live = jnp.sum(x_inputs["positions"][slots:, 0]
                               < served.max_seq)
                last = slots + jnp.maximum(live - 1, 0)

                def sampled_rows(x):
                    return jnp.concatenate([
                        x[:slots],
                        jax.lax.dynamic_slice_in_dim(x, last, 1)])

                hidden, temperature = map(sampled_rows,
                                          (hidden, temperature))
            elif q > 1:
                hidden = hidden[jnp.arange(rows), read_idx][:, None]
            sel = self._apply_tail(params, hidden)
            # a step that only decodes gathers by `read_idx` behind the
            # head: the gather keeps XLA from fusing the sampler into the
            # head's matmul, and the two apart read 0.01-0.05 ms a step
            # faster on a v5e than the fused form (PERF.md, PR 56)
            sel = (sel[:, 0] if rows > slots or q > 1
                   else sel[jnp.arange(rows), read_idx])
            sel = sel.astype(jnp.float32)  # (slots [+ 1], vocab)
            t = temperature.astype(jnp.float32)[:, None]
            gumbel = jax.random.gumbel(rng, sel.shape, jnp.float32)
            noisy = jnp.where(t > 0.0,
                              sel / jnp.maximum(t, 1e-6) + gumbel, sel)
            next_tok = jnp.argmax(noisy, axis=-1).astype(jnp.int32)
            if last is not None:
                next_tok = jax.lax.dynamic_update_slice_in_dim(
                    jnp.zeros((rows,), jnp.int32).at[:slots].set(
                        next_tok[:slots]), next_tok[slots:], last, 0)
            return self._pin_at_rest(
                self._restore_state_dtypes(new_state)), next_tok

        self._decode_step = jax.jit(
            decode_step, donate_argnums=_donate_argnums((1,)))
        return self._decode_step

    def build_verify_step(self):
        """Speculative-decoding verification as a donated executable:
        forward q = K+1 tokens per slot through the decode graph (the
        incremental-attention ops already take (slots, q) positions —
        the chunked-prefill multi-token path) and return EVERY row's
        greedy argmax, (slots, q) int32 — row j is the target's token
        for position `positions[s, j] + 1`. The host compares the
        drafter's proposals against this vector to accept the longest
        matching prefix + one correction token (serving/speculative.py);
        greedy-only by construction, which is what keeps speculative
        streams bit-identical to plain decode. Distinct draft lengths
        retrace into their own cached executables — the draft-length
        bucket set falls out of jit's shape specialization, like the
        prefill buckets. Donating `state` updates the KV cache in place;
        rejected rows need no device-side rollback — the host rewinds
        its position cursor and the next call's writes land over them
        before any masked read can see them."""

        def verify_step(params, state, x_inputs):
            logits, new_state, _ = self._apply(
                params, state,
                self._cast_compute(x_inputs), training=False, rng=None,
            )
            toks = jnp.argmax(logits.astype(jnp.float32),
                              axis=-1).astype(jnp.int32)  # (slots, q)
            return self._pin_at_rest(
                self._restore_state_dtypes(new_state)), toks

        self._verify_step = jax.jit(
            verify_step, donate_argnums=_donate_argnums((1,)))
        return self._verify_step

    def build_block_copy(self, pools: dict):
        """Copy-on-write support for the paged KV layout: duplicate pool
        blocks src[i] → dst[i] across every leaf of `pools`, {state node
        name: the leaves its op declares by block}, in one donated
        dispatch (block ids are uniform over the layers of a cache group,
        so one (src, dst) vector serves the group's stack; a group whose
        ids are its own has a program of its own: serving/paged.py). The
        serving engine pads the vectors to a power-of-two width with
        (scratch → scratch) no-op pairs, so the executable set stays
        O(log slots·chunk) like the prefill buckets. Donating `state`
        updates the pools in place on backends with donation — a COW costs
        one block-sized DMA per layer, never a pool-sized allocation."""

        def copy_blocks(state, src, dst):
            new_state = {}
            for name, ws in state.items():
                nw = dict(ws)
                for pool in pools.get(name, ()):
                    nw[pool] = nw[pool].at[dst].set(nw[pool][src])
                new_state[name] = nw
            return new_state

        return jax.jit(copy_blocks, donate_argnums=_donate_argnums((0,)))

    def build_kv_inject(self, pools: list):
        """Disaggregated-serving handoff landing: write externally
        computed KV rows (the prefill pool's blocks, host-staged by the
        coordinator) into this engine's pool blocks in one donated
        dispatch. `blocks` is the (B,) physical destination vector,
        `rows_k`/`rows_v` are (layers, B, block_size, embed) stacked in
        the order of `pools`, [(state node name, its keys' leaf, its
        values')] — the same order the extraction side reads, so layer
        i's rows land in layer i's pool. The engine
        pads B to a power of two with (scratch, zero-rows) pairs, so the
        executable set stays O(log blocks-per-prompt) like the COW copy
        buckets. Donating `state` updates the pools in place on backends
        with donation — a handoff costs block-sized DMAs, never a
        pool-sized allocation."""

        def inject_blocks(state, blocks, rows_k, rows_v):
            new_state = {name: dict(ws) for name, ws in state.items()}
            for i, (name, *leaves) in enumerate(pools):
                nw = new_state[name]
                for leaf, rows in zip(leaves, (rows_k, rows_v)):
                    nw[leaf] = nw[leaf].at[blocks].set(
                        rows[i].astype(nw[leaf].dtype))
            return new_state

        return jax.jit(inject_blocks, donate_argnums=_donate_argnums((0,)))

    def build_param_gather(self):
        """The stage-3 params' full gather as ONE donated executable:
        every sharded-at-rest leaf all-gathered back to its compute
        placement (replicated over the update axes) in a single
        dispatch; non-stage-3 leaves pass through. Consume-point
        semantics: the input tree is donated, so callers REBIND
        (`tree = gather_fn(tree)`) — the carry pattern the donated-reuse
        lint enforces. Used by the bench's param-sharding legs and the
        fsdp smoke to read/verify the gathered model without one host
        round-trip per weight; a no-op identity dispatch below stage 3."""

        def gather_params(params):
            out = {}
            for name, ws in params.items():
                nw = dict(ws)
                for k in ws:
                    if (name, k) in self.gather_specs:
                        nw[k] = self._gather_param(name, k, ws[k])
                out[name] = nw
            return out

        self._gather_fn = jax.jit(
            gather_params, donate_argnums=_donate_argnums((0,)))
        return self._gather_fn

    def build_forward(self):
        def forward(params, state, x_inputs, training):
            logits, new_state, _ = self._apply(
                params, state,
                self._cast_compute(x_inputs), training=training,
                rng=jax.random.key(0),
            )
            return logits, self._restore_state_dtypes(new_state)

        self._forward_fn = jax.jit(forward, static_argnums=(3,))
        return self._forward_fn

    # ------------------------------------------------------------ data placement

    def replicate(self, tree):
        """Place leaves on the mesh (replicated) unless already mesh-placed.
        All training state must live on the mesh before the first donated
        step: donating a buffer that needs an implicit placement change
        cannot reuse it and deadlocks XLA:CPU's in-process collectives.
        Leaves that already carry a NamedSharding on this mesh (e.g. optimizer
        slots built with zeros_like over sharded params) keep their sharding."""
        repl = NamedSharding(self.mesh, PartitionSpec())

        def place(x):
            sh = getattr(x, "sharding", None)
            if isinstance(sh, NamedSharding) and sh.mesh.shape == self.mesh.shape:
                return x
            return jax.device_put(x, repl)

        return jax.tree.map(place, tree)

    def shard_batch(self, arrays: dict, specs: dict):
        out = {}
        for name, arr in arrays.items():
            spec = specs.get(name, PartitionSpec())
            out[name] = jax.device_put(arr, NamedSharding(self.mesh, spec))
        return out


# Reduction and FusedParallelOp are deliberately excluded: a (fused)
# Reduction sums partial results, changing the value.
_VALUE_PRESERVING = frozenset({
    OT.OP_REPARTITION, OT.OP_COMBINE, OT.OP_REPLICATE,
    OT.OP_PIPELINE, OT.OP_NOOP, OT.OP_IDENTITY,
})


def _terminal_compute_op(graph: Graph, node: OpNode) -> OpNode:
    """Walk back through parallel/identity ops that only re-place (not
    transform) their input, to the op that actually computed the value.
    (Reduction is excluded: it sums partial results, changing the value.)"""
    seen = set()
    while node.op_type in _VALUE_PRESERVING and node.guid not in seen:
        seen.add(node.guid)
        edges = graph.in_edges[node.guid]
        if not edges:
            break
        src = min(edges, key=lambda e: e.dst_idx)
        node = graph.nodes[src.src]
    return node


def _spec_nontrivial(spec: PartitionSpec) -> bool:
    return any(entry is not None for entry in spec)


def _donation_supported() -> bool:
    """Donation is on wherever the backend is not XLA:CPU. On the virtual
    CPU test mesh, donated aliases deadlock XLA:CPU's in-process
    collectives, so the steps there donate nothing; on a TPU the donated
    state is updated in place (the executables alias their donated
    inputs — chip_smoke.py asserts it)."""
    return jax.default_backend() != "cpu"


def _donate_argnums(nums: tuple[int, ...]) -> tuple[int, ...]:
    return nums if _donation_supported() else ()
