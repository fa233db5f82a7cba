"""Keye-VL-2.0's language model through the normal path at a small size
(PR 38): grouped-KV attention with per-head QK-norm and RoPE under a
learned top-k selection, the training-shaped op and the paged decode op
over [k ; v] rows beside an indexer pool, all experts held, each against
the float32 reference (models/keye_vl2_reference.py) on seeded weights.
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
    build_transformer_lm, keye_vl2_lm_config, keye_vl2_reference as ref,
)
from flexflow_tpu.ops.attention import AttentionFrontEnd, Indexer
from flexflow_tpu.ops.base import OpContext, get_op_def
from small_lms import engine

# hidden 64; 4 query heads over 2 KV heads of 16; an indexer of 4 heads of
# 8 that keeps 12 positions; 16 experts of 24, 4 a token, none shared
TINY = dict(
    model_type="KeyeVL2", hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    vocab_size=97, moe_intermediate_size=24, rms_norm_eps=1e-6,
    rope_theta=10000000, attention_bias=False, decoder_sparse_step=1,
    mlp_only_layers=[], norm_topk_prob=True, num_experts=16,
    num_experts_per_tok=4, use_sliding_window=False,
    sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
               "q_chunk_size": 512, "topk": 12})
SEQ = 40
# float32 against float32, as a share of the largest logit: the sums run
# in another order, nothing else differs
TOL = 2e-5


def build(config=TINY, seq=SEQ, batch=2):
    argv = sys.argv
    sys.argv = ["t", "-b", str(batch), "--mesh", "1,1,1,1",
                "--no-verify-plan"]
    try:
        cfg = FFConfig()
    finally:
        sys.argv = argv
    ff = FFModel(cfg)
    build_transformer_lm(ff, keye_vl2_lm_config(
        config, sequence_length=seq, initializer_range=0.1),
        batch_size=batch)
    ff.compile(
        optimizer=SGDOptimizer(),
        loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY],
        comp_mode=CompMode.COMP_MODE_INFERENCE)
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


def serve(ff, **kw):
    """The shared engine of these options (tests/small_lms.py), as new."""
    return engine(ff, **{**dict(slots=3, max_seq_len=SEQ, prefill_chunk=8,
                                kv_block_size=4, kv_num_blocks=48), **kw})


def forward(ff, tokens):
    pos = np.tile(np.arange(tokens.shape[1], dtype=np.int32),
                  (tokens.shape[0], 1))
    logits, _ = ff.executor.build_forward()(
        ff._params, ff._state,
        {"tokens": jnp.asarray(tokens), "positions": jnp.asarray(pos)}, False)
    return np.asarray(logits)


def decode_graph_logits(eng, seq, split, slot=1):
    """Logits of `seq` through the decode graph: the first `split` tokens
    in the engine's chunks as rows past the slots, the rest decoded one a
    step in `slot`, all through the paged pools (tests/
    test_latent_attention.py's driver)."""
    dec, ex = eng.decode_model, eng.decode_model.executor
    slots, dead = eng.spec.slots, eng.max_seq_len
    chunk = eng.spec.prefill_chunk
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


# ---------------------------------------------------------------- the model

def test_the_config_builder_reads_the_published_keys():
    c = keye_vl2_lm_config(TINY, sequence_length=8)
    assert (c.position, c.qk_norm, c.num_kv_heads, c.head_dim) == (
        "rope", "head", 2, 16)
    assert c.indexer == Indexer(n_heads=4, head_dim=8, topk=12, rope_dim=8)
    assert c.moe_routing == {"norm_topk_prob": True, "experts_held": (0, 16)}
    assert c.mlp == "moe" and c.first_k_dense == 0 and not c.attention_gate
    for key, value in (("mlp_only_layers", [1]), ("decoder_sparse_step", 2),
                       ("use_sliding_window", True)):
        with pytest.raises(NotImplementedError, match="published block"):
            keye_vl2_lm_config(dict(TINY, **{key: value}), sequence_length=8)


def test_the_embedding_is_drawn_at_its_own_range(model):
    """`embedding_range` is the embedding's alone: the matrices keep
    `initializer_range`, and without it the embedding is drawn as they
    are."""
    def spread(ff, node, weight="kernel"):
        return float(np.std(np.asarray(ff._params[node][weight],
                                       np.float32)))

    argv = sys.argv
    sys.argv = ["t", "-b", "1", "--mesh", "1,1,1,1", "--no-verify-plan"]
    try:
        cfg = FFConfig()
    finally:
        sys.argv = argv
    ff = FFModel(cfg)
    c = keye_vl2_lm_config(dict(TINY, num_hidden_layers=1), sequence_length=8,
                           initializer_range=0.1, embedding_range=1.0)
    assert (c.initializer_range, c.embedding_range) == (0.1, 1.0)
    build_transformer_lm(ff, c, batch_size=1)
    ff.compile(
        optimizer=SGDOptimizer(),
        loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY],
        comp_mode=CompMode.COMP_MODE_INFERENCE)
    assert 0.9 < spread(ff, "wte") < 1.1
    assert 0.08 < spread(ff, "lm_head") < 0.12
    assert 0.08 < spread(ff, "l0_attn", "wq") < 0.12
    assert 0.08 < spread(model, "wte") < 0.12


def test_training_shaped_graph_gives_the_references_logits(model, tokens):
    assert "wpe" not in model._params        # positions enter by RoPE only
    attn = model._params["l0_attn"]
    assert attn["q_norm"].shape == attn["k_norm"].shape == (16,)
    assert attn["wi_q"].shape == (64, 32) and attn["wi_k"].shape == (64, 8)
    assert model._params["l0_moe"]["gate"].shape == (16, 64, 24)
    got = forward(model, tokens)
    for b in range(2):
        want, _ = ref.forward(getter(model), tokens[b], TINY)
        assert error(got[b], want) < TOL


def test_the_layers_gradients_are_the_references(model):
    """The training-shaped op (dense attention under the selection's mask)
    differentiated against the reference's attention on the same weights:
    the selection is piecewise constant, so both differentiate the softmax
    over the same selected positions."""
    node = next(n for n in model.graph.topo_order()
                if n.op_type == OT.OP_MULTIHEAD_ATTENTION)
    weights = dict(model._params[node.name])
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(SEQ, 64)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(SEQ, 64)), jnp.float32)
    pos = jnp.arange(SEQ, dtype=jnp.int32)
    ctx = OpContext(training=True, mesh=model.mesh)
    fwd = get_op_def(OT.OP_MULTIHEAD_ATTENTION).forward
    names = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")

    def program(w, x):
        xn = ref.dsa.rms_norm(x, 1.0, 1e-6)[None]
        (y,), _ = fwd(node.params, [xn, xn, xn, pos[None]],
                      {**weights, **w}, None, ctx)
        return jnp.sum(y[0] * g)

    def reference(w, x):
        with jax.default_matmul_precision("highest"):
            u, *_ = ref.attention(x, {**weights, **w}, pos, TINY,
                                  scale=jnp.ones((64,)))
        return jnp.sum(u * g)

    mine = {n: weights[n] for n in names}
    got, got_x = jax.grad(program, argnums=(0, 1))(mine, x)
    want, want_x = jax.grad(reference, argnums=(0, 1))(mine, x)
    assert float(program(mine, x)) == pytest.approx(
        float(reference(mine, x)), rel=1e-5)
    for n in names:
        assert np.max(np.abs(np.asarray(want[n]))) > 0
        assert error(got[n], np.asarray(want[n])) < 1e-4, n
    assert error(got_x, np.asarray(want_x)) < 1e-4


@pytest.mark.parametrize("length,split", [(30, 19), (11, 8)],
                         ids=["over_topk", "under_topk"])
def test_chunked_prefill_then_decode_through_the_cache_is_the_full_forward(
        model, tokens, length, split):
    """Through serve()'s decode graph: a prompt in the engine's chunks as
    rows past the slots, then decoded rows, at a context over the
    selection's 12 (every later row attends a true subset) and at one
    under it (the selection is everything): logits against the
    reference's full forward."""
    eng = serve(model)
    assert eng._chunk_rows and eng._sel_cap == 12
    seq = tokens[0, :length]
    want, _ = ref.forward(getter(model), seq, TINY)
    got = decode_graph_logits(eng, seq, split=split)
    assert error(got, want) < TOL
    # the cache holds one [k ; v] row and one indexer key (in a
    # lane-aligned row) a token a layer
    state = eng.decode_model._state["l0_attn"]
    assert state["pool_kv"].shape == (48, 4, 64)
    assert state["pool_i"].shape == (48, 4, 128)
    assert "pool_k" not in state and state["sel_rows"].shape == (3, 12)
    assert eng.kv_bytes_per_layer() == 4 * 48 * 4 * (64 + 128)
    if length > 12:
        assert int(np.sum(np.asarray(state["sel_rows"])[1] >= 0)) == 12


def test_a_chunk_walks_whole_blocks_of_a_table_they_do_not_divide(
        model, tokens, monkeypatch):
    """The cell's page table is 131 wide, a prime: a chunk's context is
    walked in blocks of as many pages as KEY_BLOCK_ROWS holds, the table
    filled up with scratch pages, not a page at a time. Here 10 pages in
    blocks of 3 (and of 4, 7, 10): the same logits."""
    from flexflow_tpu.kernels import sparse_selection

    seq = tokens[1, :34]
    want, _ = ref.forward(getter(model), seq, TINY)
    for rows in (12, 16, 28, 40):
        monkeypatch.setattr(sparse_selection, "KEY_BLOCK_ROWS", rows)
        table, p, span, _ = sparse_selection._chunk_blocks(
            jnp.arange(10), 4, jnp.asarray([33]))
        assert (p, span) == (rows // 4, rows) and table.shape[0] % p == 0
        assert table.shape[0] - 10 == -10 % p
        assert error(decode_graph_logits(serve(model), seq, split=32),
                     want) < TOL


def test_selection_is_every_position_up_to_topk_and_not_beyond(tokens):
    wide = dict(TINY, sa_config=dict(TINY["sa_config"], topk=SEQ))
    dense, sparse = build(wide), build()
    got_dense, got_sparse = forward(dense, tokens), forward(sparse, tokens)
    assert error(got_sparse[:, :12], got_dense[:, :12]) < TOL
    assert error(got_sparse[:, 12:], got_dense[:, 12:]) > 1e-2
    # a cache of no more rows than top-k: the layer is served as a plain
    # grouped one (two pools, no indexer key), with the same logits
    eng = serve(dense)
    assert eng._sel_cap == 0
    state = eng.decode_model._state["l0_attn"]
    assert set(state) >= {"pool_k", "pool_v"} and "pool_i" not in state
    want, _ = ref.forward(getter(dense), tokens[0, :20], wide)
    assert error(decode_graph_logits(eng, tokens[0, :20], split=9),
                 want) < TOL


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_a_context_of_topk_or_fewer_equals_the_grouped_paged_op(impl):
    """The op under a learned selection (a cache of 256 rows, top-64)
    against the plain grouped paged op on the same weights, the paged
    kernels interpreted under `flash` (heads of 128): 3 slots decoding at
    contexts of 5 to 64 and a chunk of 8 rows under one table row. While
    no context passes 64 the two agree; one row more and they part."""
    from flexflow_tpu.ops import inc_attention as inc

    hd, kvh, heads, embed, bs, width = 128, 1, 2, 32, 8, 32
    plain = AttentionFrontEnd(embed, heads, use_bias=False, rope_theta=1e4,
                              qk_norm="head", num_kv_heads=kvh, head_size=hd)
    indexed = AttentionFrontEnd(
        embed, heads, use_bias=False, rope_theta=1e4, qk_norm="head",
        num_kv_heads=kvh, head_size=hd,
        index=Indexer(n_heads=2, head_dim=16, topk=64, rope_dim=16))
    op = get_op_def(OT.OP_PAGED_INC_MULTIHEAD_ATTENTION)
    ctx = OpContext(training=False, mesh=None)
    rng = np.random.default_rng(2)
    slots, chunk, blocks = 3, 8, 1 + 4 * width
    rows = slots + chunk

    def make(front):
        p = inc.PagedIncMultiHeadAttentionParams(
            front, width * bs, bs, blocks, impl=impl,
            cache_dtype=DataType.DT_FLOAT, chunk_from=slots)
        return p, op.weights(p, [(rows, 1, embed), (rows, 1), (rows, width)])

    (p_i, specs_i), (p_p, specs_p) = make(indexed), make(plain)
    assert p_i.selected == 64 and p_p.selected == 0
    weights = {w.name: jnp.asarray(rng.normal(size=w.shape) * 0.2,
                                   jnp.float32)
               for w in specs_i if w.trainable}
    state_i = {w.name: jnp.zeros(w.shape, jnp.int32 if w.name == "sel_rows"
                                 else jnp.float32)
               for w in specs_i if not w.trainable}
    state_p = {w.name: jnp.zeros(w.shape, jnp.float32)
               for w in specs_p if not w.trainable}
    table = (1 + np.arange(4 * width, dtype=np.int32)).reshape(4, width)
    # fill four sequences' caches to 56, 4, 30 and 63 rows, a token a call
    # (of one program an op: the calls have one shape)
    lengths = [56, 4, 30, 63]
    xs = rng.normal(size=(4, 72, embed)).astype(np.float32)

    def program(p, specs):
        mine = {w.name: weights[w.name] for w in specs if w.trainable}

        @jax.jit
        def run(state, x, positions, row_table):
            (y,), new = op.forward(
                p, [x[:, None], positions[:, None], row_table],
                {**mine, **state}, None, ctx)
            return y[:, 0], {**state, **new}

        def call(state, x, positions, row_table):
            y, state = run(state, jnp.asarray(x), jnp.asarray(positions),
                           jnp.asarray(row_table))
            return np.asarray(y), state

        return call

    call_i, call_p = program(p_i, specs_i), program(p_p, specs_p)
    for t in range(max(lengths)):
        pos = np.array([t if t < n else 10**6 for n in lengths], np.int32)
        _, state_i = call_i(state_i, xs[:, t], pos, table)
        _, state_p = call_p(state_p, xs[:, t], pos, table)
    # one step: slots 0-2 decode at their next position, the chunk's 8
    # rows continue sequence 3 from 63 to 70 (its first row sees 64)
    x = np.concatenate([xs[:3, 64], xs[3, 63:71]])
    pos = np.array([56, 4, 30, *range(63, 71)], np.int32)
    row_table = table[[0, 1, 2] + [3] * chunk]
    got, state_i = call_i(state_i, x, pos, row_table)
    want, _ = call_p(state_p, x, pos, row_table)
    scale = np.max(np.abs(want))
    assert np.max(np.abs(got[:4] - want[:4])) < 1e-5 * scale
    # the chunk's later rows see 65 to 71 positions and keep 64 of them
    assert np.max(np.abs(got[4:] - want[4:])) > 1e-4 * scale
    assert np.all(np.asarray(state_i["sel_rows"])[1, 5:] == -1)


def test_a_prefix_cache_hit_gives_the_logits_a_miss_gives(model, tokens):
    """Both pools follow the blocks: a history left in the radix cache,
    follow-ups that find it (their first write copies the shared tail
    block of BOTH pools), against engines that prefill everything."""
    history = tokens[0, :17].tolist()
    turns = [tokens[1, :5].tolist(), tokens[1, 5:9].tolist()]
    eng = serve(model)
    eng.generate([history], max_new_tokens=1)       # leaves it in the cache
    cached = [eng.generate([history + t], max_new_tokens=6)[0]
              for t in turns]
    stats = eng.stats()
    assert stats["prefix_hit_tokens"] >= 2 * 16
    assert stats["evictions"] == 0 and stats["cow_copies"] >= 2
    fresh = [serve(model, prefix_cache=False, prefix_sharing=False
                   ).generate([history + t], max_new_tokens=6)[0]
             for t in turns]
    assert cached == fresh
    for t, reply in zip(turns, cached):     # contexts of 22 to 28 > top-12
        seq = np.array(history + t + reply)
        want, _ = ref.forward(getter(model), seq, TINY)
        first = len(history + t) - 1
        assert np.array_equal(
            np.argmax(want[first:first + len(reply)], axis=-1), reply)


def test_an_interleaved_batch_equals_each_request_alone(model):
    rng = np.random.default_rng(7)
    ps = [rng.integers(0, 97, n).tolist() for n in (19, 3, 27, 8, 14)]
    eng = serve(model)
    together = eng.generate(ps, max_new_tokens=7)
    assert eng.stats()["moe_dropped"] == 0
    for p, got in zip(ps, together):
        assert got == serve(model).generate([p], max_new_tokens=7)[0]


# ------------------------------------------------------------ the front end

def test_per_head_qk_norm_is_not_the_whole_projections():
    """`qk_norm` names the norm by one field: "head" normalises each head
    over its own lanes with one scale of head_dim, "projection" (True, as
    OLMoE's builder says it) all heads together; each matches its own
    reference (`spoil="norm_projection"` is the reference of the other)."""
    rng = np.random.default_rng(4)
    s, embed, heads, kvh, hd = 12, 64, 4, 2, 16
    x = jnp.asarray(rng.normal(size=(1, s, embed)), jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    ctx = OpContext(training=False, mesh=None)
    gq = jnp.asarray(rng.uniform(0.5, 1.5, hd), jnp.float32)
    gk = jnp.asarray(rng.uniform(0.5, 1.5, hd), jnp.float32)
    w = {name: jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)
         for name, shape in (("wq", (embed, heads * hd)),
                             ("wk", (embed, kvh * hd)),
                             ("wv", (embed, kvh * hd)))}
    d = ref.Dims(heads, kvh, hd, 1e-6, 1e4, 2, 8, 1e-6, 4)
    index = {"wi_q": jnp.zeros((embed, 16)), "wi_k": jnp.zeros((embed, 8)),
             "wi_k_norm": jnp.ones((8,)), "wi_k_bias": jnp.zeros((8,)),
             "wi_w": jnp.zeros((embed, 2))}
    got = {}
    for kind, spoil in (("head", None), ("projection", "norm_projection")):
        front = AttentionFrontEnd(embed, heads, use_bias=False,
                                  rope_theta=1e4, qk_norm=kind,
                                  qk_norm_eps=1e-6, num_kv_heads=kvh,
                                  head_size=hd)
        widths = front.qk_norm_width
        assert widths == ((hd, hd) if kind == "head"
                          else (heads * hd, kvh * hd))
        scales = {"q_norm": jnp.tile(gq, widths[0] // hd),
                  "k_norm": jnp.tile(gk, widths[1] // hd)}
        q, k, v = front.qkv(ctx, {**w, **scales}, x, x, x, pos)
        with jax.default_matmul_precision("highest"):
            rq, rk, rv, *_ = ref._attention_inputs(
                x[0], jnp.ones((embed,)),
                {**w, "q_norm": gq, "k_norm": gk, **index}, pos[0], d=d,
                spoil=spoil)
        # the reference normalises its input first: so does this call
        xn = ref.dsa.rms_norm(x, 1.0, 1e-6)
        q, k, v = front.qkv(ctx, {**w, **scales}, xn, xn, xn, pos)
        assert error(q[0], np.asarray(rq).reshape(s, -1)) < 1e-5
        assert error(k[0], np.asarray(rk).reshape(s, -1)) < 1e-5
        assert error(v[0], np.asarray(rv).reshape(s, -1)) < 1e-5
        got[kind] = np.asarray(q[0])
    assert error(got["head"], got["projection"]) > 1e-2
    assert AttentionFrontEnd(embed, heads, qk_norm=True).qk_norm_width == (
        embed, embed)
    with pytest.raises(ValueError, match="qk_norm"):
        AttentionFrontEnd(embed, heads, qk_norm="heads")
    with pytest.raises(ValueError, match="rope_theta"):
        AttentionFrontEnd(embed, heads, index=Indexer(2, 8, 4, 8))


def test_the_indexers_selection_is_the_references(model, tokens):
    """The positions a slot's row attended (`sel_rows`) are the
    reference's top-k of its own index scores at that row."""
    eng = serve(model)
    seq = tokens[1, :26]
    decode_graph_logits(eng, seq, split=16)
    get = getter(model)
    x = ref._embed(get("wte", "kernel"), jnp.asarray(seq))
    d = ref._dims(TINY)
    with jax.default_matmul_precision("highest"):
        _, _, _, qi, ki, wt = ref._attention_inputs(
            x, get("l0_ln1", "scale"),
            {n: get("l0_attn", n) for n in ref.ATTENTION_WEIGHTS},
            jnp.arange(26), d=d)
        _, mask, _, _ = ref.dsa._index_block(qi[-1:], wt[-1:], ki,
                                             jnp.asarray([25]), 12)
    chosen = np.asarray(eng.decode_model._state["l0_attn"]["sel_rows"])[1]
    assert sorted(chosen.tolist()) == np.flatnonzero(
        np.asarray(mask[0])).tolist()


def test_a_chunk_step_records_what_the_chunks_rows_chose(model, tokens):
    """Every expert is held, so a prompt token routed otherwise than a
    reference routes it has another hidden state in both for the rest of
    the sequence: whoever compares needs the chunk rows' choices too. The
    decode graph's expert layers keep them (`chunk_expert_ids`, as many
    rows as the engine's prefill chunk) beside the slots' (`expert_ids`)."""
    eng = serve(model)
    seq = tokens[0, :20]
    decode_graph_logits(eng, seq, split=16)     # chunks 0..7, 8..15
    _, notes = ref.forward(getter(model), seq, TINY)
    for layer in range(2):
        state = eng.decode_model._state[f"l{layer}_moe"]
        assert state["chunk_expert_ids"].shape == (8, 4)
        assert state["expert_ids"].shape == (3, 4)
        own = np.asarray(notes[layer]["own_ids"])
        assert np.array_equal(
            np.sort(np.asarray(state["chunk_expert_ids"]), axis=1),
            np.sort(own[8:16], axis=1))
        assert np.array_equal(np.sort(np.asarray(state["expert_ids"])[1]),
                              np.sort(own[19]))
    # a training graph's layer keeps no such record
    assert "chunk_expert_ids" not in model._state["l0_moe"]


# --------------------------------------------------------------- the engine

def test_spans_say_what_the_indexer_read_and_the_attention_gathered(
        model, tokens, monkeypatch):
    from flexflow_tpu import telemetry

    seen = []
    real = telemetry.span

    def span(name, **args):
        if name in ("serve.step", "serve.prefill"):
            seen.append((name, args))
        return real(name, **args)

    monkeypatch.setattr(telemetry, "span", span)
    eng = serve(model)
    eng.generate([tokens[0, :19].tolist()], max_new_tokens=3)
    first, second, third, *steps = seen
    # a chunk of 8 rows at positions 0..7: each row scores its own prefix
    # (1 + .. + 8), the chunk's 8 keys are read from the pool once
    assert first[0] == "serve.prefill"
    assert (first[1]["ctx_rows"], first[1]["sel_rows"],
            first[1]["index_rows"]) == (36, 36, 8)
    # positions 8..15: contexts of 9 to 16, of which at most 12 attended
    assert second[1]["ctx_rows"] == sum(range(9, 17))
    assert second[1]["sel_rows"] == sum(min(c, 12) for c in range(9, 17))
    assert second[1]["index_rows"] == 16
    assert third[1]["index_rows"] == 19
    assert [s[0] for s in steps] == ["serve.step"] * 2
    assert (steps[0][1]["ctx_rows"], steps[0][1]["sel_rows"],
            steps[0][1]["index_rows"]) == (20, 12, 20)
    stats = eng.stats()
    assert stats["moe_dropped"] == 0 and stats["moe_assignments"] > 0


@pytest.mark.parametrize("how", ["speculate", "disaggregate", "extract_kv",
                                 "admit_prefilled", "contiguous"])
def test_what_the_indexer_pool_cannot_follow_is_refused(model, how):
    """The KV handoff carries pool_k / pool_v blocks and a verification
    call is several tokens a slot: a graph with a learned selection is
    refused with the module and the reason, not served wrong."""
    match = "learned sparse selection"
    if how == "speculate":
        with pytest.raises(NotImplementedError, match=match) as e:
            serve(model, speculate=True, draft_model=model)
        assert "serving/speculative.py" in str(e.value)
    elif how == "disaggregate":
        with pytest.raises(NotImplementedError, match=match) as e:
            serve(model, disaggregate=True)
        assert "serving/disagg.py" in str(e.value)
    elif how == "contiguous":
        with pytest.raises(NotImplementedError, match="paged pool only"):
            serve(model, kv_layout="contiguous")
    else:
        eng = serve(model)
        with pytest.raises(NotImplementedError, match=match) as e:
            if how == "extract_kv":
                eng.extract_kv(0, 4)
            else:
                eng.admit_prefilled(None, 0, None, None)
        assert "serving/engine.py" in str(e.value) and "l0_attn" in str(
            e.value)
