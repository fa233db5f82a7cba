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
from dataclasses import dataclass, field, replace
from typing import Optional

from ..fftype import (
    CompMode, DataType, LossType, OperatorType as OT, dtype_to_jnp,
)


# the block pool's state leaves: keys and values of multi-head attention
# (side by side in one row, `pool_kv`, under a learned selection:
# ops/inc_attention.py), the latent row of latent attention
# (ops/latent_attention.py), the indexer's key of either. Every one is
# (num_blocks, block_size, width) under the one page table; a
# copy-on-write copies them all
POOL_LEAVES = ("pool_k", "pool_v", "pool_kv", "pool_c", "pool_i")
# the per-layer KV cache's state leaves, paged and contiguous
KV_LEAVES = (*POOL_LEAVES, "cache_k", "cache_v")
# what a recurrent layer keeps a SLOT beside the pool: the delta rule's
# state and its convolution's last inputs (ops/delta_attention.py). Not
# paged, not shareable block by block, reset when a slot's row starts a
# request (position 0)
STATE_LEAVES = ("state_s", "state_conv")


def recurrent_layers(model) -> list:
    """Names of the graph's layers that carry state from token to token
    (training graph or decode graph): a prefix matched in the pool is
    useless to them without the state at its end, a rewound cursor does
    not rewind them, and the KV handoff does not carry them."""
    return [l.name for l in model.layers
            if l.op_type in (OT.OP_GATED_DELTA_ATTENTION,
                             OT.OP_GATED_DELTA_ATTENTION_DECODE)]


def refuse_recurrent(model, what: str):
    """The KV handoff, a rewound cursor and a matched prefix are sound for
    attention only: a graph with recurrent layers is refused, not served
    wrong."""
    recurrent = recurrent_layers(model)
    if recurrent:
        raise NotImplementedError(
            f"{what} cannot serve a graph with recurrent layers (gated "
            f"delta-rule attention: {recurrent[0]}, ...): their per-slot "
            f"state is neither rewound nor handed off")


def indexed_layers(model) -> list:
    """Names of the graph's layers that keep an indexer key a token beside
    their cache rows (training graph or decode graph): what moves or
    rewinds the one has to move or rewind the other."""
    def indexed(l):
        if l.op_type in (OT.OP_LATENT_ATTENTION,
                         OT.OP_PAGED_LATENT_ATTENTION):
            return True
        return (l.op_type in (OT.OP_MULTIHEAD_ATTENTION,
                              OT.OP_PAGED_INC_MULTIHEAD_ATTENTION,
                              OT.OP_INC_MULTIHEAD_ATTENTION)
                and l.params.front.index is not None)

    return [l.name for l in model.layers if indexed(l)]


def refuse_indexed(model, what: str):
    """The KV handoff carries `pool_k` / `pool_v` blocks and a
    verification call scores several tokens a slot at once: neither knows
    the indexer's pool nor the selection, so a graph with a learned
    selection is refused, not served wrong."""
    indexed = indexed_layers(model)
    if indexed:
        raise NotImplementedError(
            f"{what} cannot serve a graph with a learned sparse selection "
            f"(an indexer pool beside the cache rows: {indexed[0]}, ...): "
            f"the indexer's keys are neither handed off nor scored by a "
            f"multi-token call")


def window_layers(model) -> list:
    """Names of the graph's attention layers that attend a window of
    their past (training graph or decode graph): the serving cache's
    window group (serving/paged.py)."""
    return [l.name for l in model.layers
            if l.op_type in (OT.OP_MULTIHEAD_ATTENTION,
                             OT.OP_PAGED_INC_MULTIHEAD_ATTENTION,
                             OT.OP_INC_MULTIHEAD_ATTENTION)
            and l.params.front.window]


def refuse_windowed(model, what: str):
    """A window layer's pool holds a slot's window and nothing behind it:
    a prompt's whole extent is not there to hand off, and a block freed
    behind an advanced cursor is not there to rewind to. A graph with a
    window group is refused, not served wrong."""
    windowed = window_layers(model)
    if windowed:
        raise NotImplementedError(
            f"{what} cannot serve a graph with window attention layers "
            f"({windowed[0]}, ...): their cache group keeps a slot's "
            f"window only, which is neither handed off whole nor rolled "
            f"back")


def slot_state_bytes(model, slots: int, at_rest: DataType) -> int:
    """Bytes the recurrent layers of a training graph keep for `slots`
    slots in its decode graph: priced beside the pool."""
    import math

    import jax.numpy as jnp

    if not recurrent_layers(model):
        return 0
    from ..ops.delta_attention import GatedDeltaDecodeParams

    tail = jnp.dtype(dtype_to_jnp(at_rest)).itemsize
    shapes = [GatedDeltaDecodeParams(l.params.front, slots, 0).state_leaves
              for l in model.layers
              if l.op_type == OT.OP_GATED_DELTA_ATTENTION]
    return sum(4 * math.prod(leaves["state_s"])
               + tail * math.prod(leaves["state_conv"]) for leaves in shapes)


def cache_row_widths(layer, cached_rows: int) -> dict:
    """{pool leaf: numbers a token holds in it} of a training-graph layer
    whose decode op keeps a cache of `cached_rows` rows a slot, {} of any
    other layer."""
    if layer.op_type == OT.OP_MULTIHEAD_ATTENTION:
        return layer.params.front.cache_row_widths(cached_rows)
    if layer.op_type == OT.OP_LATENT_ATTENTION:
        return layer.params.front.cache_row_widths
    return {}


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
    windowed = set(window_layers(model))
    window_blocks = 0
    if windowed:
        window = max(l.params.front.window for l in model.layers
                     if l.name in windowed)
        from .paged import window_slot_blocks

        window_blocks = spec.kv_window_blocks or min(
            capacity, 2 * spec.slots * window_slot_blocks(
                window, spec.prefill_chunk, bs) + 1)
    if spec.kv_num_blocks:
        return spec.kv_num_blocks, window_blocks
    try:
        import jax.numpy as jnp

        from ..search.machine_model import machine_model_for_mesh

        hbm = machine_model_for_mesh(model.mesh).chip.hbm_bytes
        itemsize = jnp.dtype(dtype_to_jnp(at_rest)).itemsize
        weight_bytes = sum(
            w.size * (itemsize if jnp.issubdtype(w.dtype, jnp.floating)
                      else w.dtype.itemsize)
            for ws in (model._params or {}).values() for w in ws.values())

        def block_bytes(group) -> int:
            return sum(
                bs * width * itemsize for l in model.layers
                if (l.name in windowed) == group
                for width in cache_row_widths(l, table_width * bs).values())

        if block_bytes(False) <= 0:
            return capacity, window_blocks
        budget = (0.9 * hbm - weight_bytes
                  - window_blocks * block_bytes(True)
                  - slot_state_bytes(model, spec.slots, at_rest))
        fit = int(budget // block_bytes(False))
        return max(spec.slots + 1, min(capacity, fit)), window_blocks
    except Exception:
        # no machine model / no params yet: capacity parity is always safe
        return capacity, window_blocks


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
    from ..ops import (
        IncMultiHeadAttentionParams, PagedIncMultiHeadAttentionParams,
    )
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

    # --- inputs: (batch, seq, ...) → (slots, 1, ...); the `positions`
    # input doubles as every attention layer's position feed
    tensor_map: dict[int, object] = {}
    positions = None
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
            positions = nt
    if positions is None:
        positions = dec.create_tensor((spec.slots, 1), DataType.DT_INT32,
                                      create_grad=False, name="positions")
    state_slot = None
    if recurrent_layers(model):
        # which slot's state a row reads and writes: row i is slot i, but
        # for a prefill chunk's rows past the slots (ops/delta_attention.py)
        state_slot = dec.create_tensor((spec.slots, 1), DataType.DT_INT32,
                                       create_grad=False, name="state_slot")
    page_table = None
    if paged:
        # one page table feeds every attention layer: block ids index the
        # same physical slot across all layers' pools (vLLM's layout), so
        # the host manages ONE table per slot, not one per layer
        table_width = -(-max_seq // spec.kv_block_size)
        page_table = dec.create_tensor(
            (spec.slots, table_width), DataType.DT_INT32,
            create_grad=False, name="page_table")
        # but a group of layers that keeps another extent of a slot's past
        # has a pool size and a table of its own: the window group
        # (serving/paged.py), same logical indexing
        page_table_w = None
        if window_blocks:
            page_table_w = dec.create_tensor(
                (spec.slots, table_width), DataType.DT_INT32,
                create_grad=False, name="page_table_w")

    # --- layers, replayed name-for-name
    layer_map: dict[int, object] = {}  # train layer guid -> decode Layer
    for layer in model.layers:
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
        if layer.op_type == OT.OP_MULTIHEAD_ATTENTION:
            p = layer.params
            if not p.causal:
                raise ValueError(
                    f"{layer.name}: serving decode requires causal "
                    f"attention (non-causal layers see future tokens the "
                    f"cache does not hold yet)")
            if not (layer.inputs[0] is layer.inputs[1]
                    is layer.inputs[2]):
                raise ValueError(
                    f"{layer.name}: serving decode supports "
                    f"self-attention only (q, k, v must be one tensor)")
            if (p.kdim not in (0, p.embed_dim)
                    or p.vdim not in (0, p.embed_dim)):
                raise ValueError(
                    f"{layer.name}: kdim/vdim != embed_dim not supported "
                    f"in the decode graph")
            # the trained layer's front end goes to the decode op whole
            if p.front.selected(max_seq) and not paged:
                raise NotImplementedError(
                    f"{layer.name}: attention under a learned selection "
                    f"is served from the paged pool only "
                    f"(kv_layout='paged')")
            if paged:
                op, np_, feeds = (
                    OT.OP_PAGED_INC_MULTIHEAD_ATTENTION,
                    PagedIncMultiHeadAttentionParams(
                        p.front, max_seq, spec.kv_block_size,
                        window_blocks if p.front.window else num_blocks,
                        impl=spec.impl, cache_dtype=at_rest,
                        chunk_from=spec.slots),
                    [ins[0], positions,
                     page_table_w if p.front.window else page_table])
            else:
                op, np_, feeds = (
                    OT.OP_INC_MULTIHEAD_ATTENTION,
                    IncMultiHeadAttentionParams(p.front, max_seq,
                                                impl=spec.impl,
                                                cache_dtype=at_rest),
                    [ins[0], positions])
            new = dec._add_layer(op, np_, feeds, name=layer.name,
                                 data_type=layer.data_type)
        elif layer.op_type == OT.OP_GATED_DELTA_ATTENTION:
            from ..ops.delta_attention import GatedDeltaDecodeParams

            new = dec._add_layer(
                OT.OP_GATED_DELTA_ATTENTION_DECODE,
                GatedDeltaDecodeParams(layer.params.front, spec.slots,
                                       max_seq, cache_dtype=at_rest),
                [ins[0], positions, state_slot], name=layer.name,
                initializers=dict(layer.initializers),
                data_type=layer.data_type)
        elif layer.op_type == OT.OP_LATENT_ATTENTION:
            from ..ops.latent_attention import PagedLatentAttentionParams

            if not paged:
                raise NotImplementedError(
                    f"{layer.name}: latent attention is served from the "
                    f"paged pool only (kv_layout='paged')")
            new = dec._add_layer(
                OT.OP_PAGED_LATENT_ATTENTION,
                PagedLatentAttentionParams(
                    layer.params.front, max_seq, spec.kv_block_size,
                    num_blocks, chunk_from=spec.slots, cache_dtype=at_rest),
                [ins[0], positions, page_table], name=layer.name,
                data_type=layer.data_type)
        else:
            params = layer.params
            if layer.op_type == OT.OP_MOE_MLP and paged:
                # a chunk rides as rows past the slots (engine.py): the
                # layer records what those rows chose too
                params = replace(params, chunk_rows=spec.prefill_chunk)
            new = dec._add_layer(
                layer.op_type, params, ins, name=layer.name,
                initializers=dict(layer.initializers),
                data_type=layer.data_type, shared_op=shared)
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
    for node_name, ws in (dec._state or {}).items():
        src = (model._state or {}).get(
            model._resolve_weight_owner(node_name), {})
        for wname, old in ws.items():
            if wname in KV_LEAVES or wname in STATE_LEAVES:
                continue
            # a leaf shaped by the graph's rows (the experts a layer chose
            # for each token) is the decode graph's own
            if wname in src and tuple(src[wname].shape) == tuple(old.shape):
                ws[wname] = adopted(src[wname], old)
                moved += 1
    return moved
