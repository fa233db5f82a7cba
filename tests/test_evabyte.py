"""EvaByte through the normal path at a small size (PR 53): EVA attention
(an aligned window of 16 exact keys beside one learned summary for every
chunk of 4 of the windows already closed, under one softmax) over 4 heads
of 16, RMSNorm scaled by 1 + g, SwiGLU, an untied head; the training-shaped
graph, chunked prefill and decode through the cache of two groups (exact
rows in the window group, summaries a row a chunk in the global group), a
prefix taken from the radix cache, and the window group's aligned
accounting, against the float32 reference (models/evabyte_reference.py) on
seeded weights.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import (
    FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
)
from flexflow_tpu.fftype import CompMode, OperatorType as OT
from flexflow_tpu.models import (
    build_transformer_lm, evabyte_lm_config, evabyte_reference as ref,
)
from flexflow_tpu.ops.base import BY_BLOCK, HANDOFF, PREFIX, QUERIES, REWIND
from flexflow_tpu.serving.paged import BlockManager, window_slot_blocks

# logits of a sequence through the decode graph's hand-made tables of both
# groups: the helper is the sibling's, it reads nothing of the model
import small_lms  # noqa: E402
from test_mimo_v2_flash_serving import decode_graph_logits  # noqa: E402

PUBLISHED = dict(
    model_type="evabyte", attention_class="eva", num_chunks=None,
    attention_bias=False, hidden_act="silu", rope_scaling=None,
    rope_theta=100000, rms_norm_eps=1e-5, norm_add_unit_offset=True,
    fp32_skip_add=True, fp32_logits=True, fp32_ln=False, mixedp_attn=True,
    num_pred_heads=8, tie_word_embeddings=False)
# hidden 64, 4 heads of 16 (as many KV heads), a window of 16 in chunks of
# 4, two layers, SwiGLU of 96, 67 byte ids
TINY = dict(
    PUBLISHED, hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=4, intermediate_size=96, vocab_size=67,
    window_size=16, chunk_size=4, init_std=0.1, max_position_embeddings=128)
SEQ = 72  # four windows and a half
# float32 against float32, as a share of the largest logit: the sums run
# in another order, nothing else differs
TOL = 2e-5


def ff_config(batch, *flags):
    argv = sys.argv
    sys.argv = ["t", "-b", str(batch), "--mesh", "1,1,1,1",
                "--no-verify-plan", *flags]
    try:
        return FFConfig()
    finally:
        sys.argv = argv


def build(config=TINY, seq=SEQ, batch=2, flags=()):
    ff = FFModel(ff_config(batch, *flags))
    build_transformer_lm(ff, evabyte_lm_config(config, sequence_length=seq),
                         batch_size=batch)
    ff.compile(
        optimizer=SGDOptimizer(),
        loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY],
        comp_mode=CompMode.COMP_MODE_INFERENCE)
    # norm gains start at zeros (the scale is 1 + g): seeded ones here, so
    # that the offset and the gains both show
    rs = np.random.default_rng(1)
    for ws in ff._params.values():
        if "scale" in ws:
            ws["scale"] = jnp.asarray(
                rs.normal(size=ws["scale"].shape) * 0.1, ws["scale"].dtype)
    return ff


@pytest.fixture(scope="module")
def model():
    return build()


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(0).integers(0, 67, (2, SEQ)).astype(np.int32)


@pytest.fixture(scope="module")
def want(model, tokens):
    return ref.forward(getter(model), tokens[0], TINY, row_block=8)


def getter(ff):
    return lambda node, weight: ff._params[node][weight]


def error(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def forward(ff, tokens):
    pos = np.tile(np.arange(tokens.shape[1], dtype=np.int32),
                  (tokens.shape[0], 1))
    logits, _ = ff.executor.build_forward()(
        ff._params, ff._state,
        {"tokens": jnp.asarray(tokens), "positions": jnp.asarray(pos)}, False)
    return logits


def serve(ff, **kw):
    """The shared engine of these options (tests/small_lms.py), as new."""
    return small_lms.engine(ff, **{**dict(
        slots=3, max_seq_len=SEQ, prefill_chunk=8, kv_block_size=8,
        kv_num_blocks=40, kv_window_blocks=40, max_new_tokens=4), **kw})


# ----------------------------------------------------------------- the block

def test_the_config_builder_reads_the_published_keys():
    c = evabyte_lm_config(TINY, sequence_length=8)
    assert c.layer_pattern == ("swa", "swa")
    assert c.swa == dict(window=16, summary_chunk=4)
    assert (c.norm, c.norm_unit_offset, c.norm_eps) == ("rmsnorm", True, 1e-5)
    assert (c.position, c.rope_theta) == ("rope", 100000.0)
    assert (c.mlp, c.intermediate_size) == ("swiglu", 96)
    assert (c.fp32_residual, c.fp32_logits) == (True, True)
    assert c.initializer_range == 0.1
    assert not c.tie_embeddings and not c.attention_bias
    # every existing configuration passes the defaults
    from flexflow_tpu.models import TransformerLMConfig

    plain = TransformerLMConfig()
    assert (plain.norm_unit_offset, plain.fp32_residual,
            plain.fp32_logits) == (False, False, False)


@pytest.mark.parametrize("key, value, said", [
    ("attention_class", "mha", "attention_class 'eva'"),
    ("num_chunks", 8, "num_chunks 8"),
    ("chunk_size", 5, "chunk_size 5 does not divide window_size 16"),
    ("rope_scaling", {"type": "linear", "factor": 2.0}, "rope_scaling"),
    ("attention_bias", True, "attention_bias False"),
    ("num_key_value_heads", 2, "num_key_value_heads 4"),
])
def test_the_builder_refuses_by_name_what_it_does_not_build(key, value, said):
    with pytest.raises(NotImplementedError, match=said):
        evabyte_lm_config({**TINY, key: value}, sequence_length=8)


def test_the_front_end_is_the_layer_kinds(model):
    from flexflow_tpu.ops.attention import AttentionFrontEnd

    front = next(l for l in model.layers
                 if l.op_type == OT.OP_MULTIHEAD_ATTENTION).params.front
    assert (front.window, front.summary_chunk, front.kv_heads) == (16, 4, 4)
    assert (front.kind, front.attend_scope) == ("eva", "eva.attend")
    assert not front.plain_core
    assert set(front.cannot_follow) == {HANDOFF, REWIND, QUERIES}
    assert PREFIX not in front.cannot_follow
    assert front.rows_attended(0) == (1, 0)
    assert front.rows_attended(16) == (1, 4)
    assert front.rows_attended(37) == (6, 8)
    with pytest.raises(ValueError, match="summary_chunk summarises"):
        AttentionFrontEnd(64, 4, False, window=16, summary_chunk=5)
    with pytest.raises(ValueError, match="summary_chunk summarises"):
        AttentionFrontEnd(64, 4, False, window=16, summary_chunk=4,
                          num_kv_heads=2)
    # the defaults leave the other kinds as they were
    assert AttentionFrontEnd(64, 4, window=16).kind == "swa"
    assert AttentionFrontEnd(64, 4).kind == "gqa"


def test_the_weights_are_the_published_blocks(model):
    shapes = {n: {k: tuple(v.shape) for k, v in ws.items()}
              for n, ws in build(batch=1)._params.items()}
    assert shapes["l1_attn"] == {
        "wq": (64, 64), "wk": (64, 64), "wv": (64, 64), "wo": (64, 64),
        "phi": (4, 16), "mu_k": (4, 16)}
    assert shapes["l1_ln1"] == shapes["ln_f"] == {"scale": (64,)}
    assert shapes["l1_ffn_gate"] == shapes["l1_ffn_up"] == {
        "kernel": (64, 96)}
    assert shapes["wte"] == {"kernel": (67, 64)}
    assert shapes["lm_head"] == {"kernel": (64, 67)}  # untied
    fresh = FFModel(ff_config(1))
    build_transformer_lm(fresh, evabyte_lm_config(TINY, sequence_length=8),
                         batch_size=1)
    fresh.compile(
        optimizer=SGDOptimizer(),
        loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        comp_mode=CompMode.COMP_MODE_INFERENCE)
    p = fresh._params
    # norm gains zeros (the scale is 1 + g), phi and mu_k within
    # head_dim^-0.5, every matrix and the embedding N(0, init_std)
    assert not np.asarray(p["l0_ln1"]["scale"]).any()
    for name in ("phi", "mu_k"):
        w = np.asarray(p["l0_attn"][name])
        assert 0.1 < np.abs(w).max() <= 0.25 and abs(w.mean()) < 0.1
    assert abs(np.asarray(p["wte"]["kernel"]).std() - 0.1) < 0.02
    assert abs(np.asarray(p["l0_attn"]["wq"]).std() - 0.1) < 0.02


def test_the_published_widths_count_the_configurations_table():
    """Shapes only: the layers as the trunk builds them from the cell's
    configuration file, nothing allocated."""
    import json
    import math
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "configs", "evabyte-6.5b.json")
    with open(path) as f:
        config = json.load(f)
    ff = FFModel(ff_config(1))
    build_transformer_lm(ff, evabyte_lm_config(config, sequence_length=128),
                         batch_size=1)
    count = {}
    for l in ff.layers:
        from flexflow_tpu.ops.base import get_op_def

        specs = get_op_def(l.op_type).weights(
            l.params, [t.dims for t in l.inputs])
        count[l.name] = sum(math.prod(w.shape) for w in specs if w.trainable)
    layer = sum(n for name, n in count.items() if name.startswith("l3_"))
    assert count["l3_attn"] == 4 * 4096 * 4096 + 2 * 32 * 128
    assert round(layer / 1e6, 2) == 202.39
    assert count["wte"] == count["lm_head"] == 320 * 4096
    assert round(sum(count.values()) / 1e6, 1) == 1621.8


# ------------------------------------------------- against the reference

def test_the_whole_lm_is_the_reference(model, tokens, want):
    logits = forward(model, tokens)
    assert error(logits[0], want.logits) < TOL
    other = ref.forward(getter(model), tokens[1], TINY)
    assert error(logits[1], other.logits) < TOL
    # a sequence that ends inside a window and inside a chunk
    short = build(seq=27, batch=1)
    short._params = model._params
    got = forward(short, tokens[:1, :27])
    assert error(got[0], want.logits[:27]) < TOL


@pytest.mark.parametrize("spoil", ref.SPOILS[1:])
def test_every_spoil_moves_the_references_logits(model, tokens, want, spoil):
    off = ref.forward(getter(model), tokens[0], TINY, spoil=spoil,
                      row_block=8)
    # a bf16 residual stream is rounding, the least of them: three orders
    # above what the program is held to here all the same
    assert error(off.logits, want.logits) > (
        0.002 if spoil == "bf16_residual" else 0.02), spoil


def test_the_reference_in_blocks_is_the_reference(model, tokens, want):
    """Row blocks change no number, and a layer's cache rows come out of
    the same forward; a row in window 0 is plain causal attention."""
    parts = ref.forward(getter(model), tokens[0], TINY, row_block=32,
                        rows=[3, 17, SEQ - 1], keep_layer=1)
    assert error(parts.logits, want.logits[jnp.asarray([3, 17, SEQ - 1])]
                 ) < TOL
    assert parts.k.shape == parts.v.shape == (SEQ, 64)
    assert parts.ksum.shape == parts.vsum.shape == (SEQ // 4, 64)
    full = ref.forward(getter(model), tokens[0][:16], TINY,
                       spoil="full_causal")
    assert error(full.logits, want.logits[:16]) < TOL


def test_with_unknown_spoil_the_reference_refuses(model, tokens):
    with pytest.raises(ValueError, match="spoil is one of"):
        ref.forward(getter(model), tokens[0], TINY, spoil="nothing")


# ------------------------------------------------------- the serving state

def test_an_eva_layer_declares_leaves_in_both_groups(model):
    from flexflow_tpu.serving.decode_graph import decode_states

    eng = serve(model)
    states = decode_states(eng.decode_model)
    s = states["l0_attn"]
    assert {l.name: (l.index, l.group, l.every) for l in s.leaves} == {
        "pool_k": (BY_BLOCK, 1, 1), "pool_v": (BY_BLOCK, 1, 1),
        "pool_ksum": (BY_BLOCK, 0, 4), "pool_vsum": (BY_BLOCK, 0, 4)}
    assert (s.blocks, s.window_blocks, s.block_size) == (40, 40, 8)
    assert (s.window, s.window_aligned) == (16, True)
    assert s.names(BY_BLOCK, group=0) == ("pool_ksum", "pool_vsum")
    assert s.names(BY_BLOCK, group=1) == ("pool_k", "pool_v")
    # a block of either group covers 8 positions: 8 exact rows, 2 summaries
    assert s.bytes_of(BY_BLOCK, 1) == 2 * 8 * 64 * 4
    assert s.bytes_of(BY_BLOCK, 0) == 2 * 2 * 64 * 4
    assert s.bytes_of(BY_BLOCK) == s.bytes_of(BY_BLOCK, 0) + s.bytes_of(
        BY_BLOCK, 1)
    assert set(s.cannot) == {HANDOFF, REWIND, QUERIES}
    state = eng.decode_model._state["l0_attn"]
    assert state["pool_k"].shape == (40, 8, 64)
    assert state["pool_ksum"].shape == (40, 2, 64)
    # the layer is in both groups, the engine prices a block by group and
    # copies a group's leaves only
    assert list(eng._groups[0]) == list(eng._groups[1]) == [
        "l0_attn", "l1_attn"]
    assert eng._block_bytes == (2 * s.bytes_of(BY_BLOCK, 0),
                                2 * s.bytes_of(BY_BLOCK, 1))
    assert eng._handoff_leaves == []
    assert eng._chunk_rows and eng._chunk_query_tile(8) is None
    w = eng.block_manager.window
    assert (w.aligned, w.window, w.slot_blocks) == (True, 16, 4)
    names = {t.name for t in eng.decode_model._input_tensors}
    assert {"page_table", "page_table_w"} <= names
    op = next(l for l in eng.decode_model.layers if l.name == "l1_attn")
    assert [t.name for t in op.inputs[1:]] == ["positions", "page_table",
                                               "page_table_w"]


def test_what_the_state_cannot_follow_is_refused_by_reason(model):
    with pytest.raises(NotImplementedError, match="aligned window beside "
                       "chunk summaries"):
        model.serve(slots=2, max_seq_len=SEQ, disaggregate=True,
                    kv_block_size=8)
    with pytest.raises(NotImplementedError, match="paged pool only"):
        model.serve(slots=2, max_seq_len=SEQ, kv_layout="contiguous")
    with pytest.raises(NotImplementedError, match="blocks that hold whole "
                       "chunks"):
        model.serve(slots=2, max_seq_len=SEQ, kv_block_size=6)


@pytest.mark.parametrize("split, chunk", [
    (5, 8),    # inside window 0
    (13, 8),   # the decoded rows cross the first boundary
    (30, 8),   # a prefill chunk (rows 8-15 | 16-23 ...) crosses one
    (64, 8),   # several windows on
    (45, 6),   # chunks that begin and end inside a chunk of 4
    (41, 5),   # and straddle a window boundary off any chunk's edge
])
def test_chunked_prefill_then_decode_is_the_references_forward(
        model, tokens, want, split, chunk):
    """The first `split` tokens in the engine's chunks as rows past the
    slots, the rest decoded one a step, through hand-made tables of both
    groups: every row's logits are the reference's full forward's."""
    eng = serve(model, prefill_chunk=chunk)
    got = decode_graph_logits(eng, tokens[0], split)
    assert error(got, want.logits) < TOL
    # the summaries the steps wrote are the reference's, a row a chunk,
    # and the exact rows its keys: slot 1's blocks are 1 + W .. 2 W
    kept = ref.forward(getter(model), tokens[0], TINY, keep_layer=1)
    state = eng.decode_model._state["l1_attn"]
    W = eng.block_manager.table_width
    mine = np.arange(1 + W, 1 + 2 * W)
    ksum = np.asarray(state["pool_ksum"])[mine].reshape(-1, 64)
    assert error(ksum[:SEQ // 4], kept.ksum) < TOL
    k = np.asarray(state["pool_k"])[mine].reshape(-1, 64)
    assert error(k[:SEQ], kept.k) < TOL


def test_the_kernels_two_calls_and_their_merge_are_the_reference():
    """The paged decode kernel (interpreted here) over the window's pages
    and over the summary pages, merged by their log-sum-exp: a window of
    256 in chunks of 16 over blocks of 128, 600 bytes; a chunk's 48 rows
    through three calls of the chunk kernel (its window, the next one where
    it straddles a boundary, as rows 240-287 do, and the summaries)."""
    config = dict(TINY, window_size=256, chunk_size=16)
    ff = build(config, seq=2048, batch=1)
    toks = np.random.default_rng(3).integers(0, 67, (600,)).astype(np.int32)
    want = ref.forward(getter(ff), toks, config, row_block=128)
    # serve(): the model is this test's alone
    eng = ff.serve(slots=2, max_seq_len=2048, prefill_chunk=48,
                   kv_block_size=128, kv_num_blocks=40, kv_window_blocks=40,
                   max_new_tokens=4, impl="flash")
    got = decode_graph_logits(eng, toks, 590)
    assert error(got, want.logits) < TOL


def test_a_prefix_from_the_radix_cache_gives_a_cold_prefills_tokens(
        model, tokens):
    """Histories of 32 (a multiple of the window: no window block pinned)
    and of 37 bytes, each continued by a turn: the request finds its
    history in the cache over both groups and decodes what a cold engine
    decodes, which is the reference's greedy continuation."""
    for length in (32, 37):
        history = [int(t) for t in tokens[0][:length]]
        prompt = history + [int(t) for t in tokens[1][:9]]
        cold = serve(model, max_new_tokens=12).generate([prompt])[0]
        warm = serve(model, max_new_tokens=12)      # as new: nothing cached
        warm.generate([history], max_new_tokens=1)
        mgr = warm.block_manager
        pinned = len(mgr._wpins)
        assert pinned == -(-(length % 16) // 8)
        assert mgr.match_prefix(prompt) == length
        got = warm.generate([prompt])[0]
        assert warm.stats()["prefix_shared_tokens"] == length
        assert got == cold
        # teacher-forced: every token is the argmax of the row before it
        logits = ref.forward(getter(model), prompt + got[:-1], TINY).logits
        assert got == np.argmax(
            logits[len(prompt) - 1:], axis=-1).tolist()
        mgr.check_invariants()
        # the request crossed a boundary (history + 9 + 12 > 48) and gave
        # its closed windows' blocks back, two at a time; the history's
        # pins are where they were
        assert warm.stats()["window_blocks_freed"] >= 2
        assert warm.stats()["eva_rollovers"] == 1
        assert mgr.match_prefix(prompt) >= length


def test_the_engine_counts_what_the_layers_read(model, tokens):
    eng = serve(model, max_new_tokens=6)
    prompt = [int(t) for t in tokens[0][:30]]
    eng.generate([prompt])
    stats = eng.stats()
    # decoded rows at positions 30 .. 34 (the first token comes from the
    # chunk's last row): exact rows 15, 16, then 1, 2, 3 of the next window
    assert stats["eva_exact_rows"] == 15 + 16 + 1 + 2 + 3
    assert stats["eva_summary_rows"] == 2 * 4 + 3 * 8
    assert stats["eva_summaries_written"] == 1   # position 31
    assert stats["eva_rollovers"] == 1           # position 32
    eng.reset_stats()
    assert "eva_exact_rows" not in eng.stats()


def test_a_float32_residual_stream_under_bf16_matmuls(tokens):
    """--dtype bf16: the embedding's rows, the residual adds and the logits
    are float32, the matrices and what they multiply bf16."""
    ff = build(batch=1, flags=("--dtype", "bf16"))
    assert {str(w.dtype) for ws in ff._params.values()
            for w in ws.values()} == {"bfloat16"}
    pos = np.arange(SEQ, dtype=np.int32)[None]
    xs = {"tokens": jnp.asarray(tokens[:1]), "positions": jnp.asarray(pos)}
    jaxpr = str(jax.make_jaxpr(
        lambda p, s: ff.executor.build_forward()(p, s, xs, False))(
            ff._params, ff._state))
    assert "f32[1,72,64]" in jaxpr and "bf16[1,72,64]" in jaxpr
    logits = forward(ff, tokens[:1])
    assert logits.dtype == jnp.float32
    want = ref.forward(getter(ff), tokens[0], TINY)
    assert error(logits[0], want.logits) < 0.03


# ------------------------------------------- the window group's accounting

def manager(**kw):
    return BlockManager(**{**dict(
        num_blocks=64, block_size=8, table_width=16, cross_time=True,
        window_blocks=32, window=16, window_span=8, window_aligned=True),
        **kw})


def test_an_aligned_window_gives_its_blocks_back_at_the_boundary():
    mgr = manager()
    w = mgr.window
    assert [w.first_row(t) for t in (0, 15, 16, 31, 32, 47)] == [
        0, 0, 16, 16, 32, 32]
    assert [w.first_block(t) for t in (0, 15, 16, 40)] == [0, 0, 2, 4]
    assert w.slot_blocks == window_slot_blocks(16, 8, 8, aligned=True) == 4
    assert window_slot_blocks(2048, 256, 256, aligned=True) == 10
    assert window_slot_blocks(2048, 256, 256) == 11
    prompt = list(range(100, 130))
    assert mgr.reserve(1, len(prompt), 20, prompt=prompt)
    mgr.bind_reservation(1, 0)
    assert mgr.admit(0, prompt) == 0
    freed = []
    for lo in range(0, 48, 8):  # chunks of 8, then rows one by one
        mgr.ensure_writable(0, range(lo, lo + 8))
        freed.append(mgr.stats.window_blocks_freed)
        mgr.check_invariants()
    # nothing inside a window, both blocks of a window at its end
    assert freed == [0, 0, 2, 2, 4, 4]
    held = [b for b in mgr.window_table(0) if b]
    assert len(held) == 2
    mgr.ensure_writable(0, [48])
    assert mgr.stats.window_blocks_freed == 6
    # a chunk that straddles a boundary keeps the old window for its
    # first rows
    mgr.ensure_writable(0, range(60, 68))
    assert mgr.stats.window_blocks_freed == 6
    assert sum(b != 0 for b in mgr.window_table(0)) == 3
    mgr.ensure_writable(0, [68])
    assert mgr.stats.window_blocks_freed == 8
    with pytest.raises(ValueError, match="blocks that divide it"):
        manager(window=20)


@pytest.mark.parametrize("length, pins", [(32, 0), (37, 1), (47, 2), (16, 0)])
def test_a_history_pins_its_current_windows_blocks(length, pins):
    """A cached extent of length L is usable where the window group holds
    the blocks of [16 floor(L / 16), L): a history that ends where a window
    does pins none; the pins survive a request that crosses a boundary."""
    mgr = manager()
    history = list(range(1000, 1000 + length))
    assert mgr.reserve("h", length, 1, prompt=history)
    mgr.bind_reservation("h", 0)
    mgr.admit(0, history)
    for lo in range(0, length, 8):
        mgr.ensure_writable(0, range(lo, min(lo + 8, length)))
    mgr.register_prompt(0, history)
    mgr.release(0)
    assert len(mgr._wpins) == pins == mgr.window.blocks_held
    mgr.check_invariants()
    prompt = history + list(range(5000, 5009))
    assert mgr.match_prefix(prompt) == length
    assert mgr.reserve("r", len(prompt), 30, prompt=prompt)
    mgr.bind_reservation("r", 1)
    assert mgr.admit(1, prompt) == length
    copies = mgr.ensure_writable(1, range(length, length + 8))
    # the shared tail block is copied in both groups, where there is one
    assert sorted(c.group for c in copies) == (
        [0, 1] if length % 8 else [])
    for t in range(length + 8, length + 39):
        mgr.ensure_writable(1, [t])
    mgr.check_invariants()
    assert mgr.stats.window_blocks_freed >= 2
    mgr.release(1)
    # the history's pins are where they were, and it is matched again
    assert len(mgr._wpins) == pins
    assert mgr.match_prefix(prompt) == length
    mgr.check_invariants()


def test_a_sliding_window_is_what_it_was():
    mgr = manager(window_aligned=False)
    w = mgr.window
    assert not w.aligned
    assert [w.first_row(t) for t in (0, 15, 16, 40)] == [0, 0, 1, 25]
    assert w.slot_blocks == window_slot_blocks(16, 8, 8) == 5
    history = list(range(37))
    mgr.reserve("h", 37, 1, prompt=history)
    mgr.bind_reservation("h", 0)
    mgr.admit(0, history)
    for lo in range(0, 37, 8):
        mgr.ensure_writable(0, range(lo, min(lo + 8, 37)))
    mgr.register_prompt(0, history)
    # every block the slot holds at its prompt's end is pinned, as before
    assert len(mgr._wpins) == sum(b != 0 for b in mgr.window_table(0)) == 3


@pytest.mark.parametrize("aligned, end", [(True, 37), (False, 32)])
def test_a_match_that_ends_in_a_tail_without_its_window_block(aligned, end):
    """A finished request's tail that begins as a new turn does is the
    longer match; where it gave its window block up, the history's own end,
    which holds one, is the end to continue from. Under a sliding window
    the match falls back to a block boundary, as it did before there were
    aligned ones."""
    mgr = manager(window_aligned=aligned)
    history = list(range(1000, 1037))
    mgr.reserve("h", 37, 1, prompt=history)
    mgr.bind_reservation("h", 0)
    mgr.admit(0, history)
    for lo in range(0, 37, 8):
        mgr.ensure_writable(0, range(lo, min(lo + 8, 37)))
    mgr.register_prompt(0, history)
    mgr.release(0)
    first = history + [7, 8, 9, 10, 11, 12]
    mgr.reserve("a", len(first), 1, prompt=first)
    mgr.bind_reservation("a", 1)
    mgr.admit(1, first)
    mgr.ensure_writable(1, range(37, 43))
    mgr.register_prompt(1, first)
    mgr.release(1)
    again = history + [7, 8, 99, 98, 97]
    assert mgr.match_prefix(again) == 39  # through the first turn's tail
    tail = mgr.cache.match(first, peek=True)[1][4]
    mgr._unpin_window(tail)               # its window block is given up
    assert mgr.match_prefix(again) == end  # the history's own end
    mgr.reserve("b", len(again), 4, prompt=again)
    mgr.bind_reservation("b", 2)
    assert mgr.admit(2, again) == end
    mgr.check_invariants()
