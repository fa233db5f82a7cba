"""Decode-graph construction: the trainer's PCG, re-expressed for serving.

The serving engine does NOT fork the model definition: it replays the
trained FFModel's layer list into a fresh FFModel whose inputs are
(slots, 1)-shaped — one new token per continuous-batching slot — and
whose causal attention layers become the incremental attention ops over
per-layer KV-cache state (ops/inc_attention.py), each given the trained
layer's front end (ops/attention.py) as one value. This replay is the one
way a decode graph is made. Everything else
(embeddings, norms, MLPs, residuals, tied weights) replays verbatim with
the SAME layer names, so:

  - the trained parameters transfer to the decode graph by (node, weight)
    name — `adopt_params` re-places them under the decode plan's
    shardings;
  - the decode graph is a real PCG: `FFModel.compile` runs the same Unity
    search (the KV-cache placement priced as a parallel dim,
    search/unity.py), the same warm-start plan cache (a second serving
    compile of the same (model, slots, max_seq, mesh) is a fingerprint
    hit with zero evaluations), and the same telemetry/diagnostics hooks
    as a training compile.
"""

from __future__ import annotations

import copy
from dataclasses import dataclass, field
from typing import Optional

from ..fftype import CompMode, DataType, LossType, size_of_datatype
from ..ops.base import (  # HANDOFF .. REWIND: for the callers of `refuse`
    BY_BLOCK, BY_POSITION, BY_SLOT, HANDOFF, PREFIX, QUERIES, REWIND,
    DecodeContext, get_op_def, registered_ops,
)
from .paged import window_slot_blocks

# what a decode op reads beside its input, by the name `OpDef.decode_layer`
# gives it, in the order the decode graph's inputs are made: every attention
# layer's position feed; which slot's state a row reads and writes; the ONE
# page table every paged layer shares (block ids index the same physical
# slot across all layers' pools, vLLM's layout), and the table of the group
# of layers that keeps another extent of a slot's past, the window group
# (serving/paged.py), same logical indexing
FEEDS = ("positions", "state_slot", "page_table", "page_table_w")


def __getattr__(name):
    """POOL_LEAVES, the block pool's state leaves (a copy-on-write copies
    them all), and KV_LEAVES, the KV cache's, paged and contiguous: what
    the registered decode ops declare (`OpDef.state_leaves`), so an op
    imported later extends them."""
    kinds = {"POOL_LEAVES": (BY_BLOCK,),
             "KV_LEAVES": (BY_BLOCK, BY_POSITION)}.get(name)
    if kinds is None:
        raise AttributeError(name)
    return tuple(dict.fromkeys(
        leaf for op in registered_ops().values()
        for leaf, index in op.state_leaves.items() if index in kinds))


def decode_states(model, ctx: DecodeContext = DecodeContext()) -> dict:
    """{layer name: DecodeState} of the layers that keep state from token
    to token: a decode graph's own declarations, a training graph's those
    of the decode layers its ops make for `ctx` (what a state can follow is
    a fact of its kind, not of sizes: the default is enough to ask that)."""
    made = (get_op_def(l.op_type).decode_layer(l, ctx) for l in model.layers)
    return {layer.name: get_op_def(op_type).state(params)
            for layer, (op_type, params, _) in zip(model.layers, made)
            if get_op_def(op_type).state}


def refuse(model, what: str, *needs, error=NotImplementedError):
    """The one refusal: a graph (training graph or decode graph) with a
    layer whose state cannot follow one of `needs` (HANDOFF, REWIND,
    QUERIES, PREFIX) is refused by the first such layer's name and its
    declaration's reason, not served wrong."""
    for name, state in decode_states(model).items():
        for need in needs:
            if need in state.cannot:
                raise error(f"{what} cannot serve a graph with "
                            f"{state.cannot[need].format(layer=name)}")


@dataclass
class ServingSpec:
    """Engine-level serving parameters (model.serve(**overrides))."""

    slots: int = 4
    max_seq_len: int = 0  # 0 → the model's training sequence length
    prefill_chunk: int = 16
    max_new_tokens: int = 32  # per-request default
    eos_id: Optional[int] = None  # per-request default (None = never)
    impl: str = "auto"  # decode attention impl (auto|xla|flash)
    # KV-cache layout (--serve-kv-layout): "paged" = block pool + per-slot
    # page tables with COW prefix sharing (the default);  "contiguous" =
    # the (slots, max_seq+1, embed) per-slot region — the ablation/
    # fallback layout (docs/serving.md)
    kv_layout: str = "paged"
    kv_block_size: int = 16  # pool rows per block (paged only)
    # physical pool blocks incl. the reserved scratch block; 0 → sized
    # from the per-chip HBM budget, capped at contiguous capacity parity
    kv_num_blocks: int = 0
    # the same for the window group's pool (the layers that attend a
    # window: serving/paged.py); 0 → twice what the slots can hold at
    # once, the other half for cached prefixes' windows. Ignored by a
    # graph without such layers
    kv_window_blocks: int = 0
    prefix_sharing: bool = True  # COW prompt-prefix reuse (paged only)
    # cross-request radix prefix cache (--serve-prefix-cache): cached
    # prompt blocks survive their residents under LRU eviction; None
    # defers to config.serve_prefix_cache. False = live sharing only.
    prefix_cache: Optional[bool] = None
    # disaggregated serving: which side this decode compile serves
    # ("" unified | "prefill" | "decode") — joins the warm-start plan
    # fingerprint via config.serve_role so the two sides' plans cache
    # independently
    role: str = ""
    # extra FFConfig fields applied to the decode compile only (e.g.
    # {"search_budget": 6, "enable_parameter_parallel": True})
    config_overrides: dict = field(default_factory=dict)
    # explicit decode-plan overrides (Strategy or raw dict) — applied via
    # set_strategy, plan_source "manual"; None → search/cache/default
    strategy: object = None


def _decode_config(model, spec: ServingSpec):
    """The decode compile's FFConfig: the trainer's, minus run-lifecycle
    subsystems that belong to the training job (its checkpoints, its
    telemetry session), plus spec.config_overrides. Search flags, mesh
    axes, and the warm-start dir carry over — the decode plan is searched
    and cached with the same machinery."""
    cfg = copy.copy(model.config)  # plain copy: __post_init__ re-parses argv
    cfg.batch_size = spec.slots
    # the layout is part of the decode plan's identity: the warm-start
    # fingerprint hashes serve_kv_layout (warmstart/fingerprint.py), so a
    # contiguous and a paged plan can never share a cache address even
    # before the structural graph difference discriminates them
    cfg.serve_kv_layout = spec.kv_layout
    # the disaggregated role is part of the plan's identity too: the
    # prefill and decode sides search the same graph over different
    # sub-meshes and must never share a warm-start address
    cfg.serve_role = spec.role
    cfg.telemetry_dir = ""
    cfg.xprof_dir = ""
    cfg.diagnostics = False
    # the decode model must not grow its own controller — the ENGINE
    # owns decode-mesh elasticity (ServingEngine.replan_mesh)
    cfg.elastic = False
    cfg.checkpoint_dir = ""
    cfg.auto_resume = False
    cfg.pipeline_steps = 1
    cfg.import_strategy_file = ""
    cfg.export_strategy_file = ""
    cfg.export_strategy_computation_graph_file = ""
    for k, v in (spec.config_overrides or {}).items():
        if not hasattr(cfg, k):
            raise ValueError(f"config_overrides: FFConfig has no field {k!r}")
        setattr(cfg, k, v)
    return cfg


def resolve_pool_blocks(model, spec: ServingSpec, max_seq: int,
                        at_rest: DataType) -> tuple:
    """(blocks of the global group's pool, blocks of the window group's),
    each with its reserved scratch block 0; 0 window blocks where the graph
    has no window layer. spec.kv_num_blocks / spec.kv_window_blocks > 0
    pin them. The window group's default is twice what the slots can hold
    at once (`paged.window_slot_blocks`), capped at capacity parity. The global
    group's is sized from the per-chip HBM budget: the machine model's chip
    capacity minus the decode graph's non-pool footprint (the trained
    weights that transfer by name, at the `at_rest` dtype the decode graph
    holds them in, and the window group's pool), capped at contiguous
    capacity parity (every slot can reach max_seq), floored at one block
    per slot so the engine can always make progress. A block is priced by
    group: in every layer of the group, `bs` rows of each of that layer's
    pool leaves, in `at_rest`."""
    bs = spec.kv_block_size
    if bs < 1:
        raise ValueError(f"kv_block_size must be >= 1, got {bs}")
    table_width = -(-max_seq // bs)
    capacity = spec.slots * table_width + 1
    for name in ("kv_num_blocks", "kv_window_blocks"):
        if getattr(spec, name) and getattr(spec, name) < 2:
            raise ValueError(
                f"{name} must be >= 2 (scratch + 1), got "
                f"{getattr(spec, name)}")
    states = decode_states(model, DecodeContext(
        spec.slots, max_seq, True, bs, at_rest=at_rest,
        prefill_chunk=spec.prefill_chunk)).values()
    window = max((s.window for s in states), default=0)
    window_blocks = 0
    if window:
        window_blocks = spec.kv_window_blocks or min(
            capacity, 2 * spec.slots * window_slot_blocks(
                window, spec.prefill_chunk, bs,
                any(s.window_aligned for s in states)) + 1)
    if spec.kv_num_blocks:
        return spec.kv_num_blocks, window_blocks
    import jax.numpy as jnp

    from ..search.machine_model import machine_model_for_mesh

    # no parameters yet, or no machine model for this device: capacity
    # parity is always safe (a mistake in a declaration raises)
    if model._params is None:
        return capacity, window_blocks
    try:
        hbm = machine_model_for_mesh(model.mesh).chip.hbm_bytes
    except ValueError:
        return capacity, window_blocks
    itemsize = size_of_datatype(at_rest)
    weight_bytes = sum(
        w.size * (itemsize if jnp.issubdtype(w.dtype, jnp.floating)
                  else w.dtype.itemsize)
        for ws in model._params.values() for w in ws.values())

    block, block_w = (sum(s.bytes_of(BY_BLOCK, group) for s in states)
                      for group in (0, 1))
    if block <= 0:
        return capacity, window_blocks
    budget = (0.9 * hbm - weight_bytes - window_blocks * block_w
              - spec.slots * sum(s.bytes_of(BY_SLOT) for s in states))
    return (max(spec.slots + 1, min(capacity, int(budget // block))),
            window_blocks)


def infer_max_seq_len(model) -> int:
    """Default KV-cache length: the training graph's sequence extent (dim 1
    of the first embedding-consuming input), so decode never outruns the
    learned positional table."""
    for t in model._input_tensors:
        if len(t.dims) >= 2:
            return int(t.dims[1])
    raise ValueError("cannot infer max_seq_len: no rank-2 input "
                     "(pass max_seq_len explicitly)")


def build_decode_model(model, spec: ServingSpec):
    """Replay `model`'s layers into a compiled decode FFModel.

    Raises for graphs serving can't express yet: non-causal or
    cross-attention (decode needs self-attention with a causal order), and
    ops whose shape inference rejects (slots, 1, ...) activations."""
    from ..model import FFModel
    from ..optimizer import SGDOptimizer

    if spec.kv_layout not in ("contiguous", "paged"):
        raise ValueError(
            f"kv_layout must be 'contiguous' or 'paged', got "
            f"{spec.kv_layout!r}")
    max_seq = spec.max_seq_len or infer_max_seq_len(model)
    paged = spec.kv_layout == "paged"
    dec = FFModel(_decode_config(model, spec))
    # what the decode graph's tensors rest in: the compute dtype where
    # the config sets one, so that no step casts what it reads every
    # token (the executor holds an inference compile's parameters so;
    # the KV cache is declared so here), float32 otherwise. The trained
    # model's fp32 masters are another model's and stay as they are.
    at_rest = dec.config.computation_dtype or DataType.DT_FLOAT
    num_blocks, window_blocks = (
        resolve_pool_blocks(model, spec, max_seq, at_rest) if paged
        else (0, 0))
    ctx = DecodeContext(spec.slots, max_seq, paged, spec.kv_block_size,
                        num_blocks, window_blocks, spec.impl, at_rest,
                        spec.prefill_chunk)
    made = [get_op_def(l.op_type).decode_layer(l, ctx) for l in model.layers]

    # --- inputs: (batch, seq, ...) → (slots, 1, ...), then the feeds a decode
    # layer reads or the engine always stages (`positions`, the page table)
    tensor_map: dict[int, object] = {}
    feeds = {}
    for t in model._input_tensors:
        if len(t.dims) < 2:
            raise ValueError(
                f"serving input {t.name!r} is rank {len(t.dims)}; decode "
                f"inputs need a (batch, seq, ...) shape")
        nt = dec.create_tensor((spec.slots, 1) + tuple(t.dims[2:]),
                               t.dtype, create_grad=False, name=t.name)
        if hasattr(t, "constant_value"):
            nt.constant_value = t.constant_value
        tensor_map[t.tensor_guid] = nt
        if t.name == "positions":
            feeds[t.name] = nt
    read = {"positions", *(("page_table",) if paged else ())}.union(
        *(reads for _, _, reads in made))
    table_width = -(-max_seq // spec.kv_block_size)
    for name, width in zip(FEEDS, (1, 1, table_width, table_width)):
        if name in read and name not in feeds:
            feeds[name] = dec.create_tensor(
                (spec.slots, width), DataType.DT_INT32, create_grad=False,
                name=name)

    # --- layers, replayed name-for-name
    layer_map: dict[int, object] = {}  # train layer guid -> decode Layer
    for layer, (op_type, params, reads) in zip(model.layers, made):
        ins = []
        for t in layer.inputs:
            mapped = tensor_map.get(t.tensor_guid)
            if mapped is None:
                raise ValueError(
                    f"layer {layer.name!r} reads a tensor serving did not "
                    f"replay ({t.name!r})")
            ins.append(mapped)
        shared = None
        if layer.shared_layer_guid >= 0:
            src = layer_map.get(layer.shared_layer_guid)
            if src is None:
                raise ValueError(
                    f"{layer.name}: tied-weight source layer not replayed")
            shared = src
        how = dict(initializers=dict(layer.initializers), shared_op=shared)
        if reads:
            # a layer re-made as a decode op takes its weights by adoption
            # and keeps its own state: no initializer, no tie is replayed
            ins, how = [ins[0], *(feeds[name] for name in reads)], {}
        new = dec._add_layer(op_type, params, ins, name=layer.name,
                             data_type=layer.data_type, **how)
        layer_map[layer.layer_guid] = new
        for t_out, d_out in zip(layer.outputs, new.outputs):
            tensor_map[t_out.tensor_guid] = d_out

    if spec.strategy is not None:
        dec.set_strategy(spec.strategy)
    # an inference compile never updates or donates its parameters, so the
    # decode model takes them as they lie where dtype and placement agree
    # (executor.init_variables): one copy of the weights on the device
    if model.config.computation_mode == CompMode.COMP_MODE_INFERENCE:
        dec._shared_variables = {
            (model._resolve_weight_owner(node), wname): w
            for node, ws in (model._params or {}).items()
            for wname, w in ws.items()}
    dec.compile(optimizer=SGDOptimizer(lr=0.0),
                loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                comp_mode=CompMode.COMP_MODE_INFERENCE)
    # what the step reads its layout from (Executor.build_decode_step):
    # the call's rows beside these slots, a dead row's position, max_seq
    dec.executor.decode_context = ctx
    return dec, max_seq


def adopt_params(dec, model) -> int:
    """Copy the trained model's parameters into the decode model by
    (node, weight) name, each cast once to the dtype the decode model
    holds it in (the compute dtype under --dtype bf16: the cast every
    step made at first use, made here) and re-placed under the decode
    plan's sharding. The copy is made on the device and is the decode
    model's own: the trainer's masters stay fp32 and stay its to donate.
    A weight the decode model already shares with an inference compile
    (build_decode_model) is that model's array and is not copied.
    Non-trainable state with a matching name/shape (e.g. BatchNorm stats)
    transfers too; the KV caches keep their zero init. Returns weights
    adopted."""
    import jax
    import jax.numpy as jnp

    def adopted(val, old):
        return jax.device_put(jnp.array(val, old.dtype), old.sharding)

    moved = 0
    for node_name, ws in dec._params.items():
        src = model._params[model._resolve_weight_owner(node_name)]
        for wname, old in ws.items():
            val = src[wname]
            if val is old:
                moved += 1
                continue
            if tuple(val.shape) != tuple(old.shape):
                raise ValueError(
                    f"{node_name}.{wname}: trained shape {val.shape} != "
                    f"decode shape {old.shape}")
            ws[wname] = adopted(val, old)
            moved += 1
    declared = decode_states(dec)  # a decode op's state keeps its zero init
    for node_name, ws in (dec._state or {}).items():
        if node_name in declared:
            continue
        src = (model._state or {}).get(
            model._resolve_weight_owner(node_name), {})
        for wname, old in ws.items():
            # a leaf shaped by the graph's rows (the experts a layer chose
            # for each token) is the decode graph's own
            if wname in src and tuple(src[wname].shape) == tuple(old.shape):
                ws[wname] = adopted(src[wname], old)
                moved += 1
    return moved
