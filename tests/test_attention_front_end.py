"""The seam of ops/attention.py's `AttentionFrontEnd`: the three attention
ops declare its weights and no others of their own, and the search and the
hand-written strategy shard them by its one head-parallel rule. What the
three ops compute from it is held token for token by tests/test_serving.py
(greedy decode against the teacher-forced forward, paged against
contiguous)."""

import sys

import pytest

from flexflow_tpu.fftype import OperatorType as OT
from flexflow_tpu.ops import (
    AttentionFrontEnd, IncMultiHeadAttentionParams, MultiHeadAttentionParams,
    PagedIncMultiHeadAttentionParams,
)

SLOTS, SEQ, WIDTH, HEADS, MAX_SEQ, BLOCK, BLOCKS = 2, 8, 32, 4, 16, 4, 9
OPS = [OT.OP_MULTIHEAD_ATTENTION, OT.OP_INC_MULTIHEAD_ATTENTION,
       OT.OP_PAGED_INC_MULTIHEAD_ATTENTION]


def _params_and_inputs(op, front):
    """(params, input shapes, the names of the op's own cache entries)"""
    x = (SLOTS, SEQ, WIDTH)
    if op == OT.OP_MULTIHEAD_ATTENTION:
        return MultiHeadAttentionParams(front, causal=True), [x, x, x], []
    if op == OT.OP_INC_MULTIHEAD_ATTENTION:
        return (IncMultiHeadAttentionParams(front, MAX_SEQ),
                [x, (SLOTS, SEQ)], ["cache_k", "cache_v"])
    return (PagedIncMultiHeadAttentionParams(front, MAX_SEQ, BLOCK, BLOCKS),
            [x, (SLOTS, SEQ), (SLOTS, MAX_SEQ // BLOCK)],
            ["pool_k", "pool_v"])


@pytest.mark.parametrize("op", OPS, ids=lambda op: op.name)
def test_attention_ops_share_one_front_end(op):
    from flexflow_tpu.ops import get_op_def

    for front in (AttentionFrontEnd(WIDTH, HEADS),
                  AttentionFrontEnd(WIDTH, HEADS, use_bias=False),
                  AttentionFrontEnd(WIDTH, HEADS, use_bias=False,
                                    qk_norm=True)):
        params, in_shapes, cache = _params_and_inputs(op, front)
        specs = get_op_def(op).weights(params, in_shapes)
        assert ([ws for ws in specs if ws.trainable]
                == front.weight_specs(WIDTH, WIDTH, WIDTH))
        assert [ws.name for ws in specs if not ws.trainable] == cache
        assert (params.embed_dim, params.num_heads, params.use_bias) == (
            front.embed_dim, front.num_heads, front.use_bias)
    names = [ws.name for ws in AttentionFrontEnd(WIDTH, HEADS, qk_norm=True)
             .weight_specs(WIDTH, WIDTH, WIDTH)]
    assert names == ["wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo",
                     "q_norm", "k_norm"]


@pytest.mark.parametrize("op", OPS, ids=lambda op: op.name)
def test_head_parallel_rule_has_one_source(op):
    from test_joint_search import _pcg_of

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.fftype import DataType
    from flexflow_tpu.machine import AXIS_MODEL, build_mesh
    from flexflow_tpu.parallel.strategies import megatron_transformer
    from flexflow_tpu.search.unity import UnitySearch

    sys.argv = ["test"]
    config = FFConfig()
    config.mesh_axis_sizes = (2, 2, 1, 1)
    config.enable_attribute_parallel = True
    ff = FFModel(config)
    front = AttentionFrontEnd(WIDTH, HEADS)
    params, in_shapes, cache = _params_and_inputs(op, front)
    x = ff.create_tensor(in_shapes[0], name="x")
    ints = [ff.create_tensor(s, DataType.DT_INT32, name=f"i{n}")
            for n, s in enumerate(in_shapes[1:]) if len(s) == 2]
    ff._add_layer(op, params, [x, x, x] if not ints else [x, *ints],
                  name="attn")

    graph = _pcg_of(ff)
    node = next(n for n in graph.topo_order() if n.name == "attn")
    search = UnitySearch(graph, build_mesh(config.mesh_shape()), config, None)
    (tp,) = [c for c in search.node_configs(node) if c.name == "tp_attn"]
    rule = front.head_parallel(AXIS_MODEL)
    assert tp.weight_specs[:len(rule)] == rule
    assert [w for w, _ in tp.weight_specs[len(rule):]] == cache
    assert tp.psum_axes == (AXIS_MODEL,)
    # a degree the heads do not divide over offers no head-parallel plan
    assert not front.head_parallel_ok(3) and front.head_parallel_ok(2)
    if op == OT.OP_MULTIHEAD_ATTENTION:
        written = megatron_transformer(ff).overrides["attn"]["weights"]
        assert written == dict(rule)
