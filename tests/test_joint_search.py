"""Joint Unity search tests: rewrites × placement DP in one optimizer
(reference base_optimize + Graph::optimal_cost, substitution.cc:2229-2311 +
graph.cc:1742-1843). Verifies the joint search is never worse than either
half alone, that sequence-splitting bounds wall time on a bench-scale LM,
and that a jointly-searched model still trains to convergence."""

import sys
import time

import numpy as np
import pytest


def _config(mesh_axes, batch=16, argv=()):
    sys.argv = ["test"] + list(argv)
    from flexflow_tpu import FFConfig

    config = FFConfig()
    config.mesh_axis_sizes = mesh_axes
    config.batch_size = batch
    return config


def _build_transformer_graph(config, layers=2):
    """Small encoder stack (attention + MLP) as a PCG, logits marked."""
    from flexflow_tpu import ActiMode, FFModel

    ff = FFModel(config)
    x = ff.create_tensor((config.batch_size, 32, 64), name="x")
    t = x
    for i in range(layers):
        a = ff.multihead_attention(t, t, t, 64, 4, name=f"l{i}_attn")
        t = ff.dense(a, 256, ActiMode.AC_MODE_RELU, name=f"l{i}_ffn1")
        t = ff.dense(t, 64, name=f"l{i}_ffn2")
    t = ff.dense(t, 16, name="head")
    return ff, t


def _pcg_of(ff):
    """Lower the builder's layers to a PCG without compiling (mirrors the
    compile() lowering)."""
    from flexflow_tpu.fftype import OperatorType as OT
    from flexflow_tpu.pcg.graph import Graph, OpNode
    from flexflow_tpu.tensor import ParallelTensor, ParallelTensorShape

    g = Graph()
    tensor_to_out = {}
    for t in ff._input_tensors:
        node = OpNode(OT.OP_INPUT, None, name=t.name)
        shape = ParallelTensorShape.from_shape(t.dims, t.dtype)
        node.outputs = [ParallelTensor(shape, name=t.name)]
        g.add_node(node)
        tensor_to_out[t.tensor_guid] = (node, 0)
    for layer in ff.layers:
        node = OpNode(layer.op_type, layer.params, name=layer.name,
                      layer_guid=layer.layer_guid,
                      initializers=layer.initializers)
        g.add_node(node)
        for dst_idx, t_in in enumerate(layer.inputs):
            src_node, src_idx = tensor_to_out[t_in.tensor_guid]
            g.add_edge(src_node, node, src_idx, dst_idx)
            node.inputs.append(src_node.outputs[src_idx])
        in_shapes = [t.dims for t in layer.inputs]
        node.weight_specs = node.op_def.weights(layer.params, in_shapes)
        for i, t_out in enumerate(layer.outputs):
            shape = ParallelTensorShape.from_shape(t_out.dims, t_out.dtype)
            pt = ParallelTensor(shape, name=t_out.name)
            pt.owner_op, pt.owner_idx = node, i
            node.outputs.append(pt)
            tensor_to_out[t_out.tensor_guid] = (node, i)
    return g


def _mesh_for(config):
    from flexflow_tpu.machine import build_mesh

    return build_mesh(config.mesh_shape())


def _joint_cost_of(graph, mesh, config, cm):
    from flexflow_tpu.search.joint import derive_pinned_configs
    from flexflow_tpu.search.unity import UnitySearch

    us = UnitySearch(graph, mesh, config, cm,
                     pinned=derive_pinned_configs(graph, mesh))
    choice = us.run()
    t, mem = us.evaluate(choice)
    return us._memory_penalized(t, mem)


def test_joint_beats_both_halves_transformer():
    """The joint optimum must cost <= the substitution-only result and <=
    the placement-DP-only result on the same transformer PCG."""
    config = _config((2, 4, 1, 1),
                     argv=["--budget", "8"])
    ff, _ = _build_transformer_graph(config)
    mesh = _mesh_for(config)

    from flexflow_tpu.search.cost_model import CostModel
    from flexflow_tpu.search.joint import joint_graph_optimize
    from flexflow_tpu.search.machine_model import machine_model_for_mesh
    from flexflow_tpu.search.substitution import (
        base_optimize, evaluate_graph, generate_all_pcg_xfers,
    )
    from flexflow_tpu.search.unity import UnitySearch

    cm = CostModel(machine_model_for_mesh(mesh))

    # half 1: substitution-only (fixed degree-derived pricing)
    g1 = _pcg_of(ff)
    xfers = generate_all_pcg_xfers(mesh, config)
    _, subst_cost = base_optimize(g1, mesh, cm, xfers, budget=8,
                                  alpha=config.search_alpha)

    # half 2: placement DP only (no rewrites)
    g2 = _pcg_of(ff)
    us = UnitySearch(g2, mesh, config, cm)
    choice = us.run()
    t, mem = us.evaluate(choice)
    dp_cost = us._memory_penalized(t, mem)

    # joint
    g3 = _pcg_of(ff)
    best_g, best_choice, us3 = joint_graph_optimize(g3, mesh, config, cm)
    jt, jmem = us3.evaluate(best_choice)
    joint_cost = us3._memory_penalized(jt, jmem)

    # evaluators are shared, so the comparison is apples-to-apples
    assert joint_cost <= dp_cost * 1.0001
    assert joint_cost <= subst_cost * 1.0001


def test_joint_beats_both_halves_dlrm():
    """Same dominance property on the DLRM PCG (branchy: towers + MLPs)."""
    config = _config((2, 4, 1, 1), argv=["--budget", "6"])
    from flexflow_tpu import FFModel
    from flexflow_tpu.models import build_dlrm

    ff = FFModel(config)
    build_dlrm(ff, batch_size=config.batch_size)
    mesh = _mesh_for(config)

    from flexflow_tpu.search.cost_model import CostModel
    from flexflow_tpu.search.joint import joint_graph_optimize
    from flexflow_tpu.search.machine_model import machine_model_for_mesh
    from flexflow_tpu.search.substitution import (
        base_optimize, generate_all_pcg_xfers,
    )
    from flexflow_tpu.search.unity import UnitySearch

    cm = CostModel(machine_model_for_mesh(mesh))

    g1 = _pcg_of(ff)
    xfers = generate_all_pcg_xfers(mesh, config)
    _, subst_cost = base_optimize(g1, mesh, cm, xfers, budget=6,
                                  alpha=config.search_alpha)

    g2 = _pcg_of(ff)
    us = UnitySearch(g2, mesh, config, cm)
    choice = us.run()
    t, mem = us.evaluate(choice)
    dp_cost = us._memory_penalized(t, mem)

    g3 = _pcg_of(ff)
    _, best_choice, us3 = joint_graph_optimize(g3, mesh, config, cm)
    jt, jmem = us3.evaluate(best_choice)
    joint_cost = us3._memory_penalized(jt, jmem)

    assert joint_cost <= dp_cost * 1.0001
    assert joint_cost <= subst_cost * 1.0001


def test_joint_search_bounded_on_bench_scale_lm():
    """Sequence splitting keeps the joint search's wall time bounded on a
    bench-scale LM (12 layers, ~100 nodes): reference
    generic_sequence_optimize, substitution.cc:2530+."""
    config = _config((2, 4, 1, 1), batch=8,
                     argv=["--budget", "6"])
    from flexflow_tpu import FFModel
    from flexflow_tpu.models import TransformerLMConfig, build_transformer_lm

    cfg = TransformerLMConfig(
        vocab_size=512, hidden_size=128, num_heads=4, num_layers=12,
        sequence_length=64, attention_impl="xla",
    )
    ff = FFModel(config)
    build_transformer_lm(ff, cfg, batch_size=8)
    g = _pcg_of(ff)
    mesh = _mesh_for(config)

    from flexflow_tpu.search.cost_model import CostModel
    from flexflow_tpu.search.joint import joint_graph_optimize
    from flexflow_tpu.search.machine_model import machine_model_for_mesh

    cm = CostModel(machine_model_for_mesh(mesh))
    t0 = time.perf_counter()
    best_g, choice, us = joint_graph_optimize(g, mesh, config, cm)
    elapsed = time.perf_counter() - t0
    # generous CI bound; without sequence splitting + the shared segment
    # cache this takes many minutes
    assert elapsed < 120, f"joint search took {elapsed:.1f}s"
    assert best_g is not None and choice
    # nested splits collapse every synthetic boundary input: the joined
    # graph reads the model's inputs and nothing else (a boundary left in
    # is an input no batch feeds, and the first train step dies on it)
    from flexflow_tpu.fftype import OperatorType as OT

    assert (sorted(n.name for n in best_g.topo_order()
                   if n.op_type == OT.OP_INPUT)
            == sorted(t.name for t in ff._input_tensors))
    # repeated transformer blocks must hit the shared segment cache
    assert us.cache_hits > 0 or len(us._segment_cache) > 0


def test_joint_compile_trains():
    """FFModel.compile with search flags goes through the joint path and
    the resulting (possibly rewritten) model still learns."""
    from flexflow_tpu import (
        ActiMode, FFModel, LossType, MetricsType, SGDOptimizer,
    )

    config = _config((2, 4, 1, 1), batch=32,
                     argv=["--budget", "4", "--enable-parameter-parallel"])
    ff = FFModel(config)
    x = ff.create_tensor((32, 32))
    t = ff.dense(x, 64, ActiMode.AC_MODE_RELU, name="fc1")
    t = ff.dense(t, 64, ActiMode.AC_MODE_RELU, name="fc2")
    t = ff.softmax(ff.dense(t, 10, name="out"))
    ff.compile(optimizer=SGDOptimizer(lr=0.1),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.METRICS_ACCURACY])
    # searched placements came from the joint entry point
    assert ff._strategy is not None

    rs = np.random.RandomState(0)
    c = rs.randn(10, 32) * 3
    y = rs.randint(0, 10, 1024)
    xs = (c[y] + rs.randn(1024, 32)).astype(np.float32)
    ff.fit(xs, y.reshape(-1, 1).astype(np.int32), epochs=2)
    acc = ff.get_perf_metrics().get_accuracy()
    assert acc >= 0.85, acc
