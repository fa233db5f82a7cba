"""The seam between a decode op and everything that sizes, prices, copies,
hands off or refuses its state (PR 45): the op declares what it keeps
(ops/base.DecodeState, `OpDef.state`), a training layer says how its decode
layer is made (`OpDef.decode_layer`), and serving/ and executor.py read the
two. Over tiny graphs of the kinds the benchmark's cells serve: what is
declared is what is allocated and what is priced, one refusal raises where
the state cannot follow, and neither an attention operator type nor a leaf
name is spelled in serving/ or executor.py.
"""

import glob
import os
import re

import pytest

import flexflow_tpu
from flexflow_tpu.fftype import DataType, dtype_to_jnp
from flexflow_tpu.models import TransformerLMConfig
from flexflow_tpu.ops.base import (
    BY_BLOCK, BY_SLOT, HANDOFF, PREFIX, QUERIES, REWIND, get_op_def,
    registered_ops,
)
from flexflow_tpu.serving import decode_graph
from flexflow_tpu.serving.decode_graph import (
    ServingSpec, decode_states, refuse, resolve_pool_blocks,
)

import test_jamba2 as jamba
import test_keye_vl2 as keye
import test_latent_attention as latent
import test_mimo_v2_flash as mimo
import test_solar_open2 as solar


def gpt2():
    return latent.build(inference=False, lm_config=TransformerLMConfig(
        vocab_size=97, hidden_size=64, num_heads=4, num_layers=2,
        sequence_length=40, attention_impl="xla"))


PAGED = dict(slots=3, max_seq_len=40, prefill_chunk=8, kv_block_size=4)
# kind: (the training graph's builder, serve()'s keywords)
KINDS = {
    "gpt2-paged": (gpt2, PAGED),
    "gpt2-contiguous": (gpt2, dict(slots=3, max_seq_len=40, prefill_chunk=8,
                                   kv_layout="contiguous")),
    "gqa-gate+delta": (solar.build, PAGED),
    "mqa+ssm": (jamba.build, PAGED),
    "latent+indexer": (latent.build, dict(PAGED, max_seq_len=24)),
    "gqa+indexer": (keye.build, PAGED),
    "window+global": (mimo.build, PAGED),
}
SELECTION, RECURRENT, WINDOW = ("a learned sparse selection",
                                "recurrent layers", "window attention layers")
# kind: {what the state cannot follow: (the layer named, the reason)}: where
# the parent's refuse_recurrent / refuse_indexed / refuse_windowed raised
REFUSED = {
    "gpt2-paged": {}, "gpt2-contiguous": {},
    "gqa-gate+delta": dict.fromkeys((HANDOFF, REWIND, PREFIX),
                                    ("l1_attn", RECURRENT)),
    "mqa+ssm": dict.fromkeys((HANDOFF, REWIND, PREFIX),
                             ("l0_attn", RECURRENT)),
    "latent+indexer": dict.fromkeys((HANDOFF, QUERIES),
                                    ("l0_attn", SELECTION)),
    "gqa+indexer": dict.fromkeys((HANDOFF, QUERIES), ("l0_attn", SELECTION)),
    "window+global": dict.fromkeys((HANDOFF, REWIND), ("l1_attn", WINDOW)),
}


@pytest.fixture(scope="module")
def served():
    """{kind: (training model, engine)}, each built once."""
    models, out = {}, {}
    for kind, (build, kw) in KINDS.items():
        if build not in models:
            models[build] = build()
        out[kind] = (models[build], models[build].serve(**kw))
    return out


def per_lead(leaves) -> int:
    return sum(int(x.nbytes) // x.shape[0] for x in leaves)


@pytest.mark.parametrize("kind", KINDS)
def test_what_is_declared_is_what_is_allocated_and_what_is_priced(
        served, kind, monkeypatch):
    model, eng = served[kind]
    dec = eng.decode_model
    states = decode_states(dec)
    assert set(states) == {l.name for l in dec.layers
                           if l.name.endswith("_attn")}
    may_hold = {leaf for op in registered_ops().values()
                for leaf in op.state_leaves}
    for layer in dec.layers:
        held = dec._state.get(layer.name, {})
        state = states.get(layer.name)
        if state is None:
            assert not may_hold & set(held), layer.name
            continue
        table = get_op_def(layer.op_type).state_leaves
        assert set(held) == {leaf.name for leaf in state.leaves}
        for leaf in state.leaves:
            x = held[leaf.name]
            assert table[leaf.name] == leaf.index
            assert x.dtype == dtype_to_jnp(leaf.dtype)
        assert {w.name: w.shape for w in state.weight_specs(
            layer.inputs[0].dims[0])} == {n: x.shape for n, x in held.items()}
        for index in (BY_BLOCK, BY_SLOT):
            assert state.bytes_of(index) == per_lead(
                held[leaf.name] for leaf in state.leaves
                if leaf.index == index), (layer.name, index)
    if eng.block_manager is None:
        return
    # the same through resolve_pool_blocks, over the training graph: a
    # budget of the weights, 100 global blocks, the window group's blocks
    # and the slots' state, as the compiled decode model holds them
    from flexflow_tpu.search import machine_model

    class Chip:
        hbm_bytes = 0

    monkeypatch.setattr(
        machine_model, "machine_model_for_mesh",
        lambda mesh, **kw: type("M", (), {"chip": Chip})())
    # the group is a fact of a leaf (a window layer's leaves are the window
    # group's, of its declaration's `window_blocks` blocks)
    block = [per_lead(dec._state[n][leaf.name] for n, s in states.items()
                      for leaf in s.leaves
                      if leaf.index == BY_BLOCK and leaf.group == g)
             for g in (0, 1)]
    for s in states.values():
        for leaf in s.leaves:
            if leaf.index == BY_BLOCK:
                assert (s.window_blocks if leaf.group else s.blocks) > 0
                assert bool(s.window) == bool(leaf.group)
    assert tuple(block) == eng._block_bytes
    slot = per_lead(dec._state[n][leaf.name] for n, s in states.items()
                    for leaf in s.leaves if leaf.index == BY_SLOT)
    assert slot == eng._state_bytes_slot
    spec = ServingSpec(**dict(KINDS[kind][1], max_seq_len=0,
                              kv_window_blocks=10))
    weights = sum(w.size * 4 for ws in model._params.values()
                  for w in ws.values())
    Chip.hbm_bytes = (weights + 100 * block[0] + 10 * block[1]
                      + spec.slots * slot + 8) / 0.9
    assert resolve_pool_blocks(model, spec, 4000, DataType.DT_FLOAT) == (
        100, 10 if block[1] else 0)


@pytest.mark.parametrize("needs", [HANDOFF, REWIND, QUERIES, PREFIX])
@pytest.mark.parametrize("kind", KINDS)
def test_one_refusal_raises_where_the_state_cannot_follow(served, kind,
                                                          needs):
    model, eng = served[kind]
    for graph in (model, eng.decode_model):
        if needs not in REFUSED[kind]:
            refuse(graph, "a test", needs)
            continue
        layer, reason = REFUSED[kind][needs]
        with pytest.raises(NotImplementedError,
                           match=rf"^a test cannot serve a graph with "
                                 rf"{reason} \(.*{layer}, \.\.\.\): ."):
            refuse(graph, "a test", needs)
        with pytest.raises(ValueError, match=reason):
            refuse(graph, "a test", QUERIES, needs, error=ValueError)


def test_a_mistake_in_a_declaration_is_not_priced_as_capacity_parity(
        served, monkeypatch):
    """resolve_pool_blocks falls back to capacity parity where the device
    has no machine model or the graph no parameters yet, and nowhere else."""
    from flexflow_tpu.ops.base import DecodeState, StateLeaf
    from flexflow_tpu.fftype import OperatorType as OT
    from flexflow_tpu.search import machine_model

    model, _ = served["gpt2-paged"]
    spec = ServingSpec(**PAGED)
    capacity = 3 * 10 + 1

    def unknown(mesh, **kw):
        raise ValueError("unknown device_kind")

    with monkeypatch.context() as m:
        m.setattr(machine_model, "machine_model_for_mesh", unknown)
        assert resolve_pool_blocks(model, spec, 40, DataType.DT_FLOAT) == (
            capacity, 0)
    with monkeypatch.context() as m:
        m.setattr(model, "_params", None)
        assert resolve_pool_blocks(model, spec, 40, DataType.DT_FLOAT) == (
            capacity, 0)
    monkeypatch.setattr(
        get_op_def(OT.OP_PAGED_INC_MULTIHEAD_ATTENTION), "state",
        lambda p: DecodeState((StateLeaf("pool_k", BY_BLOCK, None,
                                         DataType.DT_FLOAT),),
                              blocks=p.num_blocks, block_size=p.block_size))
    with pytest.raises(TypeError):
        resolve_pool_blocks(model, spec, 40, DataType.DT_FLOAT)


def test_the_leaf_tuples_are_what_the_registered_ops_declare(served):
    pools = decode_graph.POOL_LEAVES
    assert set(pools) == {"pool_k", "pool_v", "pool_kv", "pool_c", "pool_i",
                          "pool_ksum", "pool_vsum"}
    assert set(decode_graph.KV_LEAVES) == {*pools, "cache_k", "cache_v"}
    assert len(set(pools)) == len(pools)
    with pytest.raises(AttributeError):
        decode_graph.STATE_LEAVES


SOURCES = sorted(
    glob.glob(os.path.join(os.path.dirname(flexflow_tpu.__file__),
                           "serving", "*.py"))
    + [os.path.join(os.path.dirname(flexflow_tpu.__file__), "executor.py")])


@pytest.mark.parametrize("pattern", [
    r"OP_[A-Z_]*(ATTENTION|SSM)",
    r"""["'](pool_[kvci]|pool_kv|state_[sh]|state_conv|cache_[kv])["']""",
    r"\b(refuse_recurrent|refuse_indexed|refuse_windowed|PAGED_OPS|"
    r"slot_state_bytes|recurrent_layers|indexed_layers|window_layers)\b",
    r"def cache_row_widths",
], ids=["operator-type", "leaf-name", "old-name", "row-widths"])
def test_serving_and_the_executor_spell_no_attention_op_and_no_leaf(pattern):
    assert len(SOURCES) >= 8
    found = [f"{os.path.basename(path)}:{n}: {line.strip()}"
             for path in SOURCES
             for n, line in enumerate(open(path, encoding="utf-8"), 1)
             if re.search(pattern, line)]
    assert not found, "\n".join(found)


def test_the_executor_imports_nothing_from_serving():
    executor = [p for p in SOURCES if p.endswith("executor.py")][0]
    imports = [line for line in open(executor, encoding="utf-8")
               if re.match(r"\s*(from|import)\s", line) and "serving" in line]
    assert not imports
    build = open([p for p in SOURCES if p.endswith("decode_graph.py")][0],
                 encoding="utf-8").read()
    body = build[build.index("def build_decode_model"):
                 build.index("def adopt_params")]
    assert "op_type ==" not in body and "op_type in" not in body
