"""DeepSeek-V3.2 through the normal path at a small size (PR 31): the trunk
builder's training-shaped graph, the decode graph over the paged latent
cache and the expert layer that holds a share of its experts, each against
the float32 reference (models/deepseek_v32_reference.py) on seeded weights.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import (
    FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
)
from flexflow_tpu.fftype import CompMode, DataType, OperatorType as OT
from flexflow_tpu.models import (
    TransformerLMConfig, build_transformer_lm, deepseek_v32_lm_config,
    deepseek_v32_reference as ref,
)
import small_lms

# hidden 64, 4 heads, latent 32, rotary 8, indexer 2 x 16, top-k 8, 16
# experts in 4 groups of which 2 are kept, one dense layer and two expert
# layers
TINY = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, index_n_heads=2,
    index_head_dim=16, index_topk=8, rope_theta=10000,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 16, "type": "yarn"},
    rms_norm_eps=1e-6, intermediate_size=96, first_k_dense_replace=1,
    num_hidden_layers=3, n_routed_experts=16, num_experts_per_tok=4,
    moe_intermediate_size=24, n_group=4, topk_group=2, norm_topk_prob=True,
    routed_scaling_factor=2.5, n_shared_experts=1, scoring_func="sigmoid",
    vocab_size=97)
SEQ = 24
TOL = 5e-6  # float32 against float32, as a share of the largest logit


def build(config=TINY, seq=SEQ, batch=2, flags=(), inference=True,
          lm_config=None):
    argv = sys.argv
    sys.argv = ["t", "-b", str(batch), "--mesh", "1,1,1,1",
                "--no-verify-plan", *flags]
    try:
        cfg = FFConfig()
    finally:
        sys.argv = argv
    ff = FFModel(cfg)
    build_transformer_lm(ff, lm_config or deepseek_v32_lm_config(
        config, sequence_length=seq, initializer_range=0.1),
        batch_size=batch)
    ff.compile(
        optimizer=SGDOptimizer(),
        loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY],
        comp_mode=(CompMode.COMP_MODE_INFERENCE if inference
                   else CompMode.COMP_MODE_TRAINING))
    return ff


@pytest.fixture(scope="module")
def model():
    return build()


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 97, (2, SEQ)).astype(np.int32)


def getter(ff):
    return lambda node, weight: ff._params[node][weight]


def error(got, want):
    return float(np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)))


def forward(ff, tokens):
    pos = np.tile(np.arange(tokens.shape[1], dtype=np.int32),
                  (tokens.shape[0], 1))
    logits, _ = ff.executor.build_forward()(
        ff._params, ff._state,
        {"tokens": jnp.asarray(tokens), "positions": jnp.asarray(pos)}, False)
    return np.asarray(logits)


def test_training_shaped_graph_gives_the_references_logits(model, tokens):
    got = forward(model, tokens)
    for b in range(2):
        want, _ = ref.forward(getter(model), tokens[b], TINY)
        assert error(got[b], want) < TOL


def test_absorbed_equals_expanded(model, tokens):
    """The decode op (W_uk folded into the query, W_uv after the weighted
    sum, the selected rows read from the paged latent cache) row by row
    against the expanded op on the same weights: position t as a
    single-query row, every row under the same page-table row, so that a
    row reads what the rows before it wrote."""
    from flexflow_tpu.ops.latent_attention import PagedLatentAttentionParams
    from flexflow_tpu.ops.base import OpContext, get_op_def

    node = next(n for n in model.graph.topo_order()
                if n.op_type == OT.OP_LATENT_ATTENTION)
    front, weights = node.params.front, model._params[node.name]
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, SEQ, 64)), jnp.float32)
    pos = jnp.tile(jnp.arange(SEQ), (2, 1))
    ctx = OpContext(training=False, mesh=model.mesh)
    expanded = np.asarray(get_op_def(OT.OP_LATENT_ATTENTION).forward(
        node.params, [x, pos], weights, None, ctx)[0][0])

    block, width = 4, SEQ // 4
    paged = PagedLatentAttentionParams(
        front, max_seq_len=SEQ, block_size=block, num_blocks=1 + width,
        chunk_from=SEQ, cache_dtype=DataType.DT_FLOAT)
    pools = {name: jnp.zeros((1 + width, block, w), jnp.float32)
             for name, w in front.cache_row_widths.items()}
    table = jnp.tile(1 + jnp.arange(width, dtype=jnp.int32), (SEQ, 1))
    fwd = get_op_def(OT.OP_PAGED_LATENT_ATTENTION).forward
    for b in range(2):
        (out,), state = fwd(
            paged, [x[b][:, None], pos[b][:, None], table],
            {**weights, **pools}, None, ctx)
        assert np.max(np.abs(np.asarray(out)[:, 0] - expanded[b])) < (
            1e-5 * np.max(np.abs(expanded[b])))
        # past top-k positions a row attended top-k of them, not all
        assert int(np.sum(np.asarray(state["sel_rows"])[-1] >= 0)) == 8


def serve(ff, **kw):
    """The shared engine of these options (tests/small_lms.py), as new."""
    spec = dict(slots=4, max_seq_len=32, prefill_chunk=8, kv_block_size=4,
                kv_num_blocks=64)
    return small_lms.engine(ff, **{**spec, **kw})


def decode_graph_logits(eng, seq, split, slot=1):
    """Logits of `seq` through the decode graph: the first `split` tokens
    in the engine's chunks as rows past the slots, the rest decoded one a
    step in `slot`, all through the paged latent cache."""
    dec, ex = eng.decode_model, eng.decode_model.executor
    slots, dead, chunk = eng.spec.slots, eng.max_seq_len, eng.spec.prefill_chunk
    W = eng.block_manager.table_width
    table = (1 + np.arange(slots * W, dtype=np.int32)).reshape(slots, W)

    @jax.jit
    def step(params, state, xs):
        logits, new_state, _ = ex._apply(params, state, ex._cast_compute(xs),
                                         training=False, rng=None)
        return ex._restore_state_dtypes(new_state), logits[:, 0]

    def call(toks, positions, row_slots):
        xs = {"tokens": jnp.asarray(toks), "positions": jnp.asarray(positions),
              "page_table": jnp.asarray(table[row_slots])}
        dec._state, rows = step(dec._params, dec._state, xs)
        return np.asarray(rows)

    out = []
    for start in range(0, split, chunk):
        part = seq[start:min(start + chunk, split)]
        toks = np.zeros((slots + chunk, 1), np.int32)
        positions = np.full((slots + chunk, 1), dead, np.int32)
        toks[slots:slots + len(part), 0] = part
        positions[slots:slots + len(part), 0] = np.arange(
            start, start + len(part))
        rows = call(toks, positions,
                    np.r_[np.arange(slots), np.full(chunk, slot)])
        out += list(rows[slots:slots + len(part)])
    for t in range(split, len(seq)):
        toks = np.zeros((slots, 1), np.int32)
        positions = np.full((slots, 1), dead, np.int32)
        toks[slot, 0], positions[slot, 0] = seq[t], t
        out.append(call(toks, positions, np.arange(slots))[slot])
    return np.stack(out)


def test_chunked_prefill_then_decode_through_the_cache_is_the_full_forward(
        model, tokens):
    eng = serve(model)
    assert eng._chunk_rows  # a chunk rides as rows under one table row
    seq = tokens[0, :20]
    want, _ = ref.forward(getter(model), seq, TINY)
    got = decode_graph_logits(eng, seq, split=12)
    assert error(got, want) < TOL
    # the cache holds one latent row and one indexer key a token a layer
    state = eng.decode_model._state["l0_attn"]
    assert state["pool_c"].shape == (64, 4, 128)   # 32 + 8, lane-aligned
    assert state["pool_i"].shape == (64, 4, 16)
    assert state["sel_rows"].shape == (4, 8)
    assert eng.kv_bytes_per_layer() == 4 * 64 * 4 * (128 + 16)


def test_selection_is_every_position_up_to_topk_and_not_beyond(tokens):
    """With a context no longer than top-k the result is dense latent
    attention; with a longer one it is not."""
    dense = build(dict(TINY, index_topk=SEQ))
    sparse = build()
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for n in dense._params
               for a, b in zip(dense._params[n].values(),
                               sparse._params[n].values()))
    got_dense, got_sparse = forward(dense, tokens), forward(sparse, tokens)
    k = TINY["index_topk"]
    assert error(got_sparse[:, :k], got_dense[:, :k]) < TOL
    assert error(got_sparse[:, k:], got_dense[:, k:]) > 1e-2
    # the decode graph under the wide top-k walks every cached row
    eng = serve(dense)
    want, _ = ref.forward(getter(dense), tokens[0, :20],
                          dict(TINY, index_topk=SEQ))
    assert error(decode_graph_logits(eng, tokens[0, :20], split=9),
                 want) < TOL


def test_history_from_the_radix_cache_gives_the_logits_of_a_fresh_prefill(
        model, tokens):
    history = tokens[0, :17].tolist()
    turns = [tokens[1, :5].tolist(), tokens[1, 5:9].tolist()]
    eng = serve(model)
    eng.generate([history], max_new_tokens=1)       # leaves it in the cache
    cached = [eng.generate([history + t], max_new_tokens=4)[0]
              for t in turns]
    stats = eng.stats()
    # both follow-ups found the whole history: 16 rows each served from
    # the cache (the 17th token is the last of a block's partial run)
    assert stats["prefix_hit_tokens"] >= 2 * 16
    assert stats["prompt_tokens"] == 17 + 22 + 21
    assert stats["evictions"] == 0 and stats["cow_copies"] >= 2
    fresh = [serve(model, prefix_cache=False, prefix_sharing=False
                   ).generate([history + t], max_new_tokens=4)[0]
             for t in turns]
    assert cached == fresh
    for t, reply in zip(turns, cached):
        seq = np.array(history + t + reply)
        want, _ = ref.forward(getter(model), seq, TINY)
        first = len(history + t) - 1
        assert np.array_equal(
            np.argmax(want[first:first + len(reply)], axis=-1), reply)


def test_a_follow_up_reserves_its_own_blocks_not_a_second_history():
    from flexflow_tpu.serving.paged import BlockManager

    mgr = BlockManager(num_blocks=24, block_size=4, table_width=16,
                       sharing=True, cross_time=True)
    history = list(range(30))
    assert mgr.reserve(0, 30, 1, prompt=history)
    mgr.bind_reservation(0, 0)
    mgr.admit(0, history)
    mgr.ensure_writable(0, range(30))
    mgr.register_prompt(0, history)
    mgr.release(0)
    assert mgr.cached_blocks == 8 and mgr.free_blocks == 15
    follow = history + [99, 98, 97]
    # 9 blocks worst case, of which the 7 before the first write are cached
    assert mgr.reserve(1, 33, 3, prompt=follow)
    assert mgr.reserved_total == 2 and len(mgr._held[("req", 1)]) == 8
    assert mgr.reserve(2, 33, 3)                     # without the prompt
    assert mgr.reserved_total == 2 + 9
    # pressure evicts nothing a reservation counts on
    assert not mgr.reserve(3, 40, 20)
    mgr.bind_reservation(1, 1)
    assert mgr.admit(1, follow) == 30
    assert mgr._held == {("req", 2): []} and mgr.stats.radix_evictions == 0
    mgr.check_invariants()


@pytest.mark.parametrize("router", ["sigmoid", "softmax"])
def test_the_shares_of_all_chips_add_up_to_the_whole_layer(router):
    """The expert op under each of the four sets of held experts, the
    shared expert counted once, sums to the uncut reference's layer:
    under DeepSeek-V3.2's sigmoid group-limited router, and under
    Solar-Open2's softmax router with renormalised gates (PR 33)."""
    from flexflow_tpu.models import solar_open2_reference as solar
    from flexflow_tpu.ops import MoEMLPParams
    from flexflow_tpu.ops.base import OpContext, get_op_def

    rng = np.random.default_rng(5)
    d, n, f, k = 64, 16, 24, 4
    w = {"router": rng.normal(size=(d, n)), "router_bias": 0.1 * rng.normal(
        size=(n,)), "gate": 0.2 * rng.normal(size=(n, d, f)),
        "up": 0.2 * rng.normal(size=(n, d, f)),
        "down": 0.2 * rng.normal(size=(n, f, d)),
        "shared_gate": 0.2 * rng.normal(size=(d, f)),
        "shared_up": 0.2 * rng.normal(size=(d, f)),
        "shared_down": 0.2 * rng.normal(size=(f, d))}
    w = {name: jnp.asarray(a, jnp.float32) for name, a in w.items()}
    x = jnp.asarray(rng.normal(size=(40, d)), jnp.float32)
    if router == "sigmoid":
        cfg, layer = ref.model_cfg(dict(TINY)), ref.expert_layer
        routing_fields = dict(scoring="sigmoid", n_group=4, topk_group=2,
                              routed_scaling_factor=2.5)
    else:
        w.pop("router_bias")
        cfg, layer = dict(num_experts_per_tok=k, norm_topk_prob=True,
                          routed_scaling_factor=1), solar.expert_layer
        routing_fields = dict(scoring="softmax")

    def held(first, count):
        return {**w, **{name: w[name][first:first + count]
                        for name in ("gate", "up", "down")}}

    with jax.default_matmul_precision("highest"):
        whole, routing = layer(x, w, cfg, held=(0, n))
        g = x @ w["shared_gate"]
        shared = (g * jax.nn.sigmoid(g) * (x @ w["shared_up"])
                  ) @ w["shared_down"]
    fwd = get_op_def(OT.OP_MOE_MLP).forward
    ctx = OpContext(training=False, mesh=None)
    total = np.zeros_like(np.asarray(whole))
    assignments = 0
    for first in range(0, n, 4):
        p = MoEMLPParams(
            n, k, f, norm_topk_prob=True, shared_intermediate_size=f,
            experts_held=(first, 4), **routing_fields)
        (y,), state = fwd(p, [x], held(first, 4), None, ctx)
        with jax.default_matmul_precision("highest"):
            share, _ = layer(x, held(first, 4), cfg, held=(first, 4))
        assert error(y, np.asarray(share)) < TOL
        assert np.array_equal(np.asarray(state["expert_ids"]),
                              np.asarray(routing["ids"]))
        assert int(state["dropped_total"]) == 0
        assignments += int(state["assignments_total"])
        total += np.asarray(y) - np.asarray(shared)
    assert assignments == 40 * k      # every assignment computed once
    assert error(total + np.asarray(shared), np.asarray(whole)) < TOL


def test_a_chunk_step_records_the_slots_experts():
    """The expert op's record of its choices (`expert_ids`, declared for
    the slots' rows) in a step that carries a prefill chunk as rows past
    the slots: the slots' rows, which come first; any other layout leaves
    the record as it was."""
    from flexflow_tpu.ops import MoEMLPParams
    from flexflow_tpu.ops.base import OpContext, get_op_def

    rng = np.random.default_rng(6)
    d, n, f, k = 64, 16, 24, 4
    w = {"router": rng.normal(size=(d, n)), "router_bias": np.zeros(n),
         "gate": rng.normal(size=(n, d, f)), "up": rng.normal(size=(n, d, f)),
         "down": rng.normal(size=(n, f, d))}
    w = {name: jnp.asarray(a, jnp.float32) for name, a in w.items()}
    p = MoEMLPParams(n, k, f, scoring="sigmoid", n_group=4, topk_group=2,
                     norm_topk_prob=True, routed_scaling_factor=2.5)
    fwd = get_op_def(OT.OP_MOE_MLP).forward
    ctx = OpContext(training=False, mesh=None)
    x = jnp.asarray(rng.normal(size=(12, 1, d)), jnp.float32)
    was = jnp.full((4, k), -7, jnp.int32)
    _, alone = fwd(p, [x[:4]], {**w, "expert_ids": was}, None, ctx)
    _, chunk = fwd(p, [x], {**w, "expert_ids": was}, None, ctx)
    assert np.array_equal(np.asarray(chunk["expert_ids"]),
                          np.asarray(alone["expert_ids"]))
    assert np.asarray(alone["expert_ids"]).min() >= 0
    _, rectangle = fwd(p, [x.reshape(4, 3, d)], {**w, "expert_ids": was},
                       None, ctx)
    assert "expert_ids" not in rectangle


def _cell_scores(rng, S):
    """A decode step's 16 rows at a cell's width: sums of ReLUs with exact
    zeros (a run of them; one row's across the k-th place), NEG behind a
    row's length of 8-33 k."""
    from flexflow_tpu.kernels.sparse_selection import NEG

    lengths = np.exp(np.linspace(np.log(8555), np.log(S - 1), 16))
    index = np.maximum(rng.normal(size=(16, 2, S)), 0).sum(axis=1)
    index[:, S // 30:S // 10] = 0.0
    index[3] = np.where(rng.random(S) < 0.97, 0.0, index[3])
    index[np.arange(S)[None, :] >= lengths.astype(int)[:, None]] = NEG
    return index


def _small_scores(rng):
    from flexflow_tpu.kernels.sparse_selection import NEG

    index = rng.normal(size=(6, 40))
    index[1, rng.integers(0, 40, 25)] = 0.0  # a ReLU's zeros
    index[2] = np.round(index[2])            # many ties
    index[3, 7:] = NEG                       # 7 candidates
    index[4] = NEG                           # a dead row
    index[5] = -np.abs(index[5])
    return index


def _seen(rng, S, lengths):
    """Random scores (rows, S), NEG from each row's length on."""
    from flexflow_tpu.kernels.sparse_selection import NEG

    return np.where(np.arange(S)[None, :] < np.asarray(lengths)[:, None],
                    rng.normal(size=(len(lengths), S)), NEG)


# name -> rng -> (index scores (rows, S), k)
SELECTION_CASES = {
    "small_k1": lambda rng: (_small_scores(rng), 1),
    "small_k5": lambda rng: (_small_scores(rng), 5),
    "small_k16": lambda rng: (_small_scores(rng), 16),
    "small_k40": lambda rng: (_small_scores(rng), 40),
    "keye2_cell": lambda rng: (_cell_scores(rng, 33536), 2048),
    "dsv32_cell": lambda rng: (_cell_scores(rng, 33280), 2048),
    "zeros_across_the_kth_place": lambda rng: (
        np.where(rng.random((4, 3000)) < 0.9, 0.0, 1.0), 500),
    "all_scores_equal": lambda rng: (np.full((3, 1000), 0.25), 128),
    "negative_scores": lambda rng: (-np.abs(rng.normal(size=(4, 700))), 200),
    "fewer_candidates_than_k": lambda rng: (
        _seen(rng, 900, [5, 199, 200, 201]), 200),
    "a_dead_row": lambda rng: (_seen(rng, 600, [600, 0, 600]), 128),
    "S_at_most_k": lambda rng: (_seen(rng, 300, [300, 41, 0]), 2048),
    "k_of_no_whole_lane_tile": lambda rng: (
        np.round(rng.normal(size=(5, 1500)), 1), 200),
    "one_row": lambda rng: (rng.normal(size=(1, 5000)), 2048),
    "S_of_no_whole_word": lambda rng: (
        np.round(rng.normal(size=(3, 1001)), 1), 333),
}


@pytest.mark.parametrize("case", list(SELECTION_CASES))
def test_selection_mask_is_the_exact_top_k_without_a_sort(case):
    """`select_topk` and `selection_mask` share their bisection and their
    ties, so both are held to numpy: a stable argsort of the negated
    scores, the first K of a row's candidates (`lax.top_k`'s set, ties to
    the lower position). `select_topk`'s `valid` is a prefix with that
    count, its positions ascending inside the prefix and in range behind
    it."""
    from flexflow_tpu.kernels import sparse_selection as sel

    index, k = SELECTION_CASES[case](
        np.random.default_rng(sum(map(ord, case))))
    index = index.astype(np.float32)
    rows, S = index.shape
    mask = np.asarray(jax.jit(sel.selection_mask, static_argnums=1)(
        jnp.asarray(index), k))
    picked, valid = map(np.asarray, jax.jit(
        sel.select_topk, static_argnums=1)(jnp.asarray(index), k))
    K = min(k, S)
    assert picked.shape == valid.shape == (rows, K) and mask.shape == (rows, S)
    assert picked.dtype == np.int32 and valid.dtype == bool
    assert picked.min() >= 0 and picked.max() < S
    for r in range(rows):
        order = np.argsort(-index[r], kind="stable")
        want = order[index[r][order] > sel.NEG / 2][:K]
        count = int(valid[r].sum())
        assert count == len(want) and valid[r, :count].all()
        assert np.array_equal(picked[r, :count], np.sort(want))
        assert np.array_equal(np.flatnonzero(mask[r]), np.sort(want))


def test_a_chunks_dense_attention_is_its_rows_sparse_attention():
    """`attend_chunk` (one pass over the shared context under the mask)
    gives what `attend_selected` gives row by row (top-k, gathered
    rows)."""
    from flexflow_tpu.kernels import sparse_latent_attention as sla

    rng = np.random.default_rng(4)
    blocks, bs, W, rows, heads, latent, rope = 40, 4, 8, 6, 3, 16, 8
    pool = jnp.asarray(rng.normal(size=(blocks, bs, latent + rope)),
                       jnp.float32)
    table = jnp.asarray(rng.permutation(blocks - 1)[:W] + 1, jnp.int32)
    q = jnp.asarray(rng.normal(size=(rows, heads, latent + rope)),
                    jnp.float32)
    pos = jnp.asarray([20, 21, 22, 23, 24, -1], jnp.int32)  # one dead row
    index = jnp.where(jnp.arange(W * bs)[None] <= pos[:, None],
                      jnp.asarray(rng.normal(size=(rows, W * bs)),
                                  jnp.float32), sla.NEG)
    sel, valid = sla.select_topk(index, 9)
    sparse = sla.attend_selected(
        q, pool, jnp.broadcast_to(table, (rows, W)), sel, valid,
        latent_dim=latent, scale=0.3)
    dense = sla.attend_chunk(q, pool, table, sla.selection_mask(index, 9),
                             pos, latent_dim=latent, scale=0.3)
    assert np.allclose(np.asarray(dense[:5]), np.asarray(sparse[:5]),
                       atol=1e-5)
    assert np.all(np.asarray(dense[5]) == 0)


def test_one_copy_of_the_weights_serves_an_inference_compile(tokens):
    """serve() on an inference compile takes the weights as they lie
    (bf16 under --dtype bf16); on a trainer it makes its own copy."""
    ff = build(flags=("--dtype", "bf16"))
    assert all(w.dtype == jnp.bfloat16 for ws in ff._params.values()
               for name, w in ws.items() if w.ndim >= 2)
    eng = serve(ff)
    dec = eng.decode_model
    assert all(dec._params[n][w] is ff._params[n][w]
               for n in ff._params for w in ff._params[n])
    assert eng.adopted >= sum(len(ws) for ws in ff._params.values())
    assert dec._state["l0_attn"]["pool_c"].dtype == jnp.bfloat16
    assert eng.generate([tokens[0, :9].tolist()], max_new_tokens=3)
    trainer = build(inference=False)
    dec = serve(trainer).decode_model
    assert not any(dec._params[n][w] is trainer._params[n][w]
                   for n in trainer._params for w in trainer._params[n])


def test_pool_blocks_are_priced_from_the_layers_own_rows(monkeypatch):
    from flexflow_tpu.search import machine_model
    from flexflow_tpu.serving import decode_graph

    class Chip:
        hbm_bytes = 0

    monkeypatch.setattr(
        machine_model, "machine_model_for_mesh",
        lambda mesh, **kw: type("M", (), {"chip": Chip, "num_hosts": 1})())

    def blocks(ff, hbm, itemsize=4):
        weights = sum(w.size * itemsize for ws in ff._params.values()
                      for w in ws.values())
        Chip.hbm_bytes = (weights + hbm) / 0.9
        spec = decode_graph.ServingSpec(slots=2, kv_block_size=4)
        return decode_graph.resolve_pool_blocks(ff, spec, 4000,
                                                DataType.DT_FLOAT)[0]

    latent = build()
    # 3 layers x 4 rows x (128 + 16) numbers x 4 bytes a block
    assert blocks(latent, 100 * 3 * 4 * 144 * 4 + 8) == 100
    gpt2 = build(lm_config=TransformerLMConfig(
        vocab_size=97, hidden_size=64, num_heads=4, num_layers=2,
        sequence_length=SEQ, attention_impl="xla"))
    # GPT-2's figure as before: 2 x block x embed a layer
    assert blocks(gpt2, 100 * 2 * (2 * 4 * 64) * 4 + 8) == 100


def test_spans_say_what_the_indexer_scored_and_the_experts_computed(
        model, tokens, monkeypatch):
    from flexflow_tpu import telemetry

    seen = []
    real = telemetry.span

    def span(name, **args):
        if name in ("serve.step", "serve.prefill"):
            seen.append((name, args))
        return real(name, **args)

    monkeypatch.setattr(telemetry, "span", span)
    eng = serve(build(dict(TINY, n_routed_experts=4, experts_held=[4, 4],
                           experts_routed=16)))
    eng.generate([tokens[0, :11].tolist()], max_new_tokens=3)
    first, second, *steps = seen
    # chunk of 8 rows at positions 0..7: each scores and reads its prefix
    assert first[0] == "serve.prefill" and first[1]["ctx_rows"] == 36
    assert first[1]["sel_rows"] == 36
    # positions 8..10 score 9, 10, 11 rows and read top-8 of them
    assert (second[1]["ctx_rows"], second[1]["sel_rows"]) == (30, 24)
    assert [s[0] for s in steps] == ["serve.step"] * 2
    assert steps[0][1]["ctx_rows"] == 12 and steps[0][1]["sel_rows"] == 8
    stats = eng.stats()
    assert stats["moe_dropped"] == 0
    assert 0 < stats["moe_assignments"] < (12 + 8 + 4 + 4) * 8
    eng.reset_stats()
    assert eng.stats()["moe_assignments"] == 0


def test_olmoe_and_gpt2_blocks_are_built_as_before():
    """The new fields' defaults leave the two older blocks' graphs and
    weight names alone (tests/test_olmoe.py holds them layer by layer)."""
    from flexflow_tpu.models import olmoe_lm_config

    for cfg, names in (
            (TransformerLMConfig(vocab_size=97, hidden_size=64, num_heads=4,
                                 num_layers=1, sequence_length=8,
                                 attention_impl="xla"),
             {"l0_attn": {"wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo"},
              "l0_ffn1": {"kernel", "bias"}}),
            (olmoe_lm_config(vocab_size=97, hidden_size=64, num_heads=4,
                             num_layers=1, sequence_length=8,
                             attention_impl="xla", num_experts=4,
                             num_experts_per_tok=2, moe_intermediate_size=16),
             {"l0_attn": {"wq", "wk", "wv", "wo", "q_norm", "k_norm"},
              "l0_moe": {"router", "gate", "up", "down"}})):
        ff = build(lm_config=cfg, inference=False)
        for node, weights in names.items():
            assert set(ff._params[node]) == weights
    assert set(ff._state["l0_moe"]) == {
        "dropped_tokens", "load_max_over_mean", "expert_ids"}


def test_olmoe_block_serves_rope_and_qk_norm_through_the_decode_ops():
    """Rotary positions and QK-norm reach the incremental attention ops:
    greedy decode through the paged cache is the training graph's
    argmax."""
    from flexflow_tpu.models import olmoe_lm_config

    ff = build(lm_config=olmoe_lm_config(
        vocab_size=97, hidden_size=64, num_heads=4, num_layers=2,
        sequence_length=SEQ, attention_impl="xla", num_experts=4,
        num_experts_per_tok=2, moe_intermediate_size=16), inference=False)
    prompt = np.random.default_rng(2).integers(0, 97, 9).tolist()
    reply = serve(ff, impl="xla").generate([prompt], max_new_tokens=6)[0]
    seq = np.array([prompt + reply + [0] * (SEQ - 15)], np.int32)
    logits = forward(ff, seq)[0]
    assert np.array_equal(np.argmax(logits[8:14], axis=-1), reply)
