"""Parallelization strategies: per-node mesh-axis assignments.

The reference expresses a strategy as one `MachineView` per PCG node, found by
Unity search or imported from a file (SURVEY §2.1, §2.3). Here a `Strategy` is
the TPU-native equivalent: a map

    node name → {"outputs": {out_idx: axis_assignment},
                 "weights": {weight_name: PartitionSpec}}

where axis_assignment is a tuple (one entry per tensor dim) of tuples of mesh
axis names. `FFModel.compile` applies it on top of the data-parallel default
(model.cc:get_basic_data_parallel_config analog), and the executor pins every
tensor with `with_sharding_constraint`, so the strategy is exactly what XLA
runs (GSPMD cannot silently re-propagate it away).

The hand-written generators below mirror the reference's substitution
families (substitution.cc:1726-1868):
  - megatron_transformer = create_replicate_linear_combine +
    create_partition_attention_combine applied model-wide (column→row
    parallel Linear pairs, head-parallel attention).
  - sequence_parallel_attention = the seq-dim sharding the reference lacks
    (SURVEY §5 "long-context: absent") — ring attention over the `seq` axis.
Unity search (search/) produces Strategy objects automatically; these
generators are the `--import-strategy` analog and the search's seeds.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from jax.sharding import PartitionSpec

from ..fftype import OperatorType as OT
from ..machine import AXIS_DATA, AXIS_EXPERT, AXIS_MODEL, AXIS_SEQ


@dataclass
class Strategy:
    """Per-node placement overrides, mergeable; applied at compile."""

    overrides: dict = field(default_factory=dict)

    def node(self, name: str) -> dict:
        return self.overrides.setdefault(name, {"outputs": {}, "weights": {}})

    def set_output(self, name: str, out_idx: int, assignment):
        self.node(name)["outputs"][out_idx] = tuple(tuple(a) for a in assignment)

    def set_weight(self, name: str, weight_name: str, spec: PartitionSpec):
        self.node(name)["weights"][weight_name] = spec

    def merge(self, other: "Strategy") -> "Strategy":
        out = Strategy({k: {"outputs": dict(v["outputs"]),
                            "weights": dict(v["weights"])}
                        for k, v in self.overrides.items()})
        for k, v in other.overrides.items():
            n = out.node(k)
            n["outputs"].update(v["outputs"])
            n["weights"].update(v["weights"])
        return out

    def __bool__(self):
        return bool(self.overrides)

    # -------------------------------------------------- JSON (de)serialization
    # The --export-strategy / --import-strategy file format
    # (model.cc:3599-3608 analog; the reference's protobuf strategy file
    # becomes JSON here). A searched plan can be saved once and replayed
    # without re-searching — the AE two-run pattern re-uses one search.

    def to_json(self) -> dict:
        def spec_entry(e):
            if e is None:
                return None
            if isinstance(e, (tuple, list)):
                return list(e)
            return e

        out = {"version": 1, "nodes": {}}
        for name, ov in self.overrides.items():
            out["nodes"][name] = {
                "outputs": {
                    str(idx): [list(axes) for axes in assignment]
                    for idx, assignment in ov.get("outputs", {}).items()
                },
                "weights": {
                    wname: [spec_entry(spec[i]) for i in range(len(spec))]
                    for wname, spec in ov.get("weights", {}).items()
                },
            }
        return out

    @staticmethod
    def from_json(data: dict) -> "Strategy":
        if data.get("version") != 1:
            raise ValueError(
                f"unsupported strategy file version {data.get('version')!r}")
        s = Strategy()
        for name, ov in data.get("nodes", {}).items():
            for idx, assignment in ov.get("outputs", {}).items():
                s.set_output(name, int(idx),
                             tuple(tuple(a) for a in assignment))
            for wname, entries in ov.get("weights", {}).items():
                s.set_weight(name, wname, PartitionSpec(*[
                    tuple(e) if isinstance(e, list) else e for e in entries
                ]))
        return s

    def validate(self, graph, mesh) -> None:
        """Check this strategy can apply to (graph, mesh); raise ValueError
        listing every problem otherwise.

        `Strategy.load` / `--import-strategy` historically checked only the
        file `version`, so a plan exported from a different model or mesh
        silently degraded to data parallel node by node. Delegates to the
        ffcheck sharding verifier (analysis/sharding.py) — the ONE shared
        gate — so the import path, the warm-start plan cache, and
        checkpoint plan adoption inherit every verifier check, including
        the one this method historically MISSED: the same mesh axis used
        on two different dims of one assignment (an invalid NamedSharding
        that only exploded at device_put time). Checks: unknown node
        names, out-of-range output indices / rank mismatches, unknown
        weight names, mesh axes absent from the mesh, per-assignment axis
        reuse, oversharded dims, and sharded dims not divisible by their
        axes' total degree."""
        from ..analysis import verify_strategy

        verify_strategy(self.overrides, graph, mesh)

    def save(self, path: str):
        import json

        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)

    @staticmethod
    def load(path: str) -> "Strategy":
        import json

        with open(path) as f:
            return Strategy.from_json(json.load(f))


def _act_assignment(ndims: int, batch_axes=(AXIS_DATA,), last_axes=()):
    """Assignment for an activation: batch dim over data, last dim optionally
    over model, middle dims replicated."""
    a = [()] * ndims
    if ndims > 0:
        a[0] = tuple(batch_axes)
    if last_axes and ndims > 1:
        a[-1] = tuple(last_axes)
    return tuple(a)


def megatron_transformer(model, model_axis: str = AXIS_MODEL) -> Strategy:
    """Column→row parallel Linear pairs + head-parallel attention.

    Equivalent PCG rewrite in the reference: Replicate → {partitioned-weight
    Linear/Attention} → Reduction (create_replicate_linear_combine,
    substitution.cc:71-76; create_replicate_attention_reduce:91). Under GSPMD
    the Replicate/Reduction endpoints become implicit: the column-parallel
    weight shards the activation's feature dim, the row-parallel weight's
    contraction over a sharded dim makes XLA insert the psum over ICI.
    """
    s = Strategy()
    layers = getattr(model, "layers", model)
    # map tensor guid -> producing layer, for chain detection
    producer = {}
    for l in layers:
        for t in l.outputs:
            producer[t.tensor_guid] = l

    def upstream(layer):
        t = layer.inputs[0]
        return producer.get(t.tensor_guid)

    paired_row: set[int] = set()   # layer guids already made row-parallel
    paired_col: set[int] = set()

    for l in layers:
        if l.op_type == OT.OP_MULTIHEAD_ATTENTION:
            # heads split over the model axis (ops/attention.py's rule)
            for w, spec in l.params.front.head_parallel(model_axis):
                s.set_weight(l.name, w, spec)
            # output fully materialized (psum) with batch sharded
            nd = len(l.outputs[0].dims)
            s.set_output(l.name, 0, _act_assignment(nd))
        elif l.op_type == OT.OP_LINEAR and l.layer_guid not in paired_row:
            # find Linear → [elementwise activation] → Linear chains
            nxt = _linear_consumer(l, layers)
            if nxt is None or nxt.layer_guid in paired_col:
                continue
            # l = column parallel
            s.set_weight(l.name, "kernel", PartitionSpec(None, model_axis))
            if any(ws.name == "bias" for ws in _weight_specs(l)):
                s.set_weight(l.name, "bias", PartitionSpec(model_axis))
            nd = len(l.outputs[0].dims)
            s.set_output(l.name, 0, _act_assignment(nd, last_axes=(model_axis,)))
            paired_col.add(l.layer_guid)
            # activations in between stay sharded on the feature dim
            chain = _chain_between(l, nxt, producer)
            for mid in chain:
                ndm = len(mid.outputs[0].dims)
                s.set_output(mid.name, 0,
                             _act_assignment(ndm, last_axes=(model_axis,)))
            # nxt = row parallel
            s.set_weight(nxt.name, "kernel", PartitionSpec(model_axis, None))
            s.set_weight(nxt.name, "bias", PartitionSpec())
            ndn = len(nxt.outputs[0].dims)
            s.set_output(nxt.name, 0, _act_assignment(ndn))
            paired_row.add(nxt.layer_guid)
        elif l.op_type == OT.OP_EMBEDDING:
            # column-parallel table: shard the embedding dim
            s.set_weight(l.name, "kernel", PartitionSpec(None, model_axis))
    return s


def _weight_specs(layer):
    from ..ops.base import get_op_def

    in_shapes = [t.dims for t in layer.inputs]
    return get_op_def(layer.op_type).weights(layer.params, in_shapes)


_ELEMENTWISE_CHAIN_OPS = frozenset(
    {
        OT.OP_RELU, OT.OP_GELU, OT.OP_SIGMOID, OT.OP_TANH, OT.OP_ELU,
        OT.OP_IDENTITY, OT.OP_DROPOUT, OT.OP_SCALAR_MULTIPLY,
        OT.OP_SCALAR_ADD, OT.OP_SCALAR_SUB, OT.OP_SCALAR_TRUE_DIV,
    }
)


def _linear_consumer(layer, layers):
    """Return the Linear fed (possibly through elementwise ops) by `layer`."""
    out_guids = {t.tensor_guid for t in layer.outputs}
    for l in layers:
        if not l.inputs:
            continue
        if l.inputs[0].tensor_guid in out_guids:
            if l.op_type == OT.OP_LINEAR:
                return l
            if l.op_type in _ELEMENTWISE_CHAIN_OPS:
                return _linear_consumer(l, layers)
    return None


def _chain_between(src, dst, producer):
    """Elementwise layers strictly between src and dst (walk back from dst)."""
    chain = []
    cur = producer.get(dst.inputs[0].tensor_guid)
    while cur is not None and cur.layer_guid != src.layer_guid:
        chain.append(cur)
        if not cur.inputs:
            break
        cur = producer.get(cur.inputs[0].tensor_guid)
    return chain


def sequence_parallel_attention(model, seq_axis: str = AXIS_SEQ) -> Strategy:
    """Shard the sequence dim of 3D activations over `seq_axis`.

    The attention op must use impl="ring" (ring attention over ICI,
    parallel/ring_attention.py) — set via FFModel.multihead_attention(impl=
    "ring") — so KV blocks rotate through the ring while queries stay
    resident. This is the long-context capability the reference lacks
    (SURVEY §5).

    Tensors whose seq dim does not divide by the configured seq-axis
    degree are left alone (they would fail Strategy.validate / GSPMD
    lowering); with no mesh information on `model` every 3D output is
    sharded, matching the historical behavior."""
    seq_deg = 0
    cfg = getattr(model, "config", None)
    if cfg is not None:
        try:
            ms = cfg.mesh_shape()
            seq_deg = dict(zip(ms.axis_names, ms.axis_sizes)).get(seq_axis, 0)
        except Exception:
            seq_deg = 0
    s = Strategy()
    layers = getattr(model, "layers", model)
    for l in layers:
        for i, t in enumerate(l.outputs):
            if len(t.dims) == 3:
                if seq_deg > 1 and int(t.dims[1]) % seq_deg != 0:
                    continue  # indivisible seq dim: keep the default
                # (batch, seq, hidden): batch over data, seq over seq axis
                s.set_output(l.name, i, ((AXIS_DATA,), (seq_axis,), ()))
    return s


def expert_parallel_moe(model, expert_axis: str = AXIS_MODEL) -> Strategy:
    """Shard the stacked-experts weight dim of Experts ops over the expert
    axis (reference analog: attribute-parallel machine views over the MoE
    expert ops, examples/cpp/mixture_of_experts).

    Defaults to the `model` mesh axis (AXIS_EXPERT is an alias used when the
    mesh names an axis "expert" explicitly — it is not in DEFAULT_AXES)."""
    s = Strategy()
    layers = getattr(model, "layers", model)
    for l in layers:
        if l.op_type in (OT.OP_EXPERTS, OT.OP_MOE_MLP):
            for ws in _weight_specs(l):
                nd = len(ws.shape)
                if nd < 2 or ws.name == "router":
                    continue  # the router and the counters stay replicated
                s.set_weight(
                    l.name, ws.name,
                    PartitionSpec(expert_axis, *([None] * (nd - 1))),
                )
    return s
