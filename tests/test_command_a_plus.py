"""Command A+'s language model through the normal path at a small size (PR
49): a parallel block under one LayerNorm with a scale and no bias, three
window layers (interleaved RoPE over the whole head, a window of 8) to one
global layer that takes no position, 8 query heads on 2 KV heads with a
query projection twice the hidden state, a sigmoid router with no
correction bias over 16 experts beside 4 shared experts averaged, the head
tied to the embedding; the training-shaped graph, chunked prefill and
decode through the cache of two groups (contexts under the window,
crossing it while they decode, far past it), and the eight shares of a
layer, against the float32 reference
(models/command_a_plus_reference.py) on seeded weights.
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
    TransformerLMConfig, build_transformer_lm, command_a_plus_lm_config,
    command_a_plus_reference as ref,
)
from flexflow_tpu.ops.base import OpContext, get_op_def

# logits of a sequence through the decode graph's hand-made tables of both
# groups: the helper is the sibling's, it reads nothing of the model
import small_lms  # noqa: E402
from test_mimo_v2_flash_serving import decode_graph_logits  # noqa: E402

# hidden 64; 8 query heads of 16 (a query projection of 128) on 2 KV heads;
# a window of 8; layers [window, window, window, global]; 16 experts of 24,
# 4 a token, beside 4 shared experts of 24
PUBLISHED = dict(
    model_type="cohere2_moe", use_parallel_block=True, use_qk_norm=False,
    attention_bias=False, first_k_dense_replace=0, rotary_pct=1,
    position_embedding_type="rope_gptj", rope_theta=50000, hidden_act="silu",
    use_gated_activation=True, expert_selection_fn="sigmoid",
    norm_topk_prob=True, shared_expert_combination_strategy="average",
    tie_word_embeddings=True, logit_scale=1, layer_norm_eps=1e-5,
    layer_types=["sliding_attention"] * 3 + ["full_attention"])
TINY = dict(
    PUBLISHED, hidden_size=64, num_hidden_layers=4, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, vocab_size=97, intermediate_size=24,
    sliding_window=8, num_experts=16, num_experts_per_tok=4,
    num_shared_experts=4)
SEQ = 40
# float32 against float32, as a share of the largest logit: the sums run
# in another order, nothing else differs
TOL = 2e-5


def ff_config(batch):
    argv = sys.argv
    sys.argv = ["t", "-b", str(batch), "--mesh", "1,1,1,1",
                "--no-verify-plan"]
    try:
        return FFConfig()
    finally:
        sys.argv = argv


def build(config=TINY, seq=SEQ, batch=2, impl="xla"):
    ff = FFModel(ff_config(batch))
    build_transformer_lm(ff, command_a_plus_lm_config(
        config, sequence_length=seq, attention_impl=impl,
        initializer_range=0.1, embedding_range=0.1, embedding_mean=0.3),
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


def forward(ff, tokens):
    pos = np.tile(np.arange(tokens.shape[1], dtype=np.int32),
                  (tokens.shape[0], 1))
    logits, _ = ff.executor.build_forward()(
        ff._params, ff._state,
        {"tokens": jnp.asarray(tokens), "positions": jnp.asarray(pos)}, False)
    return np.asarray(logits)


# ----------------------------------------------------------------- the block

def test_the_config_builder_reads_the_published_keys():
    c = command_a_plus_lm_config(TINY, sequence_length=8)
    assert c.layer_pattern == ("swa", "swa", "swa", "mha")
    assert (c.norm, c.norm_bias, c.parallel_block) == ("layernorm", False,
                                                       True)
    # position is the layer kind's: the model has none, the window layers
    # their own theta and form
    assert c.position == "none"
    assert c.swa == dict(window=8, rope_theta=5e4, rope_interleaved=True)
    assert (c.num_heads, c.num_kv_heads, c.head_dim) == (8, 2, 16)
    assert c.tie_embeddings
    assert c.moe_routing == dict(
        scoring="sigmoid", correction_bias=False, norm_topk_prob=True,
        shared_intermediate_size=96, shared_scale=0.25, experts_held=None)
    assert c.router_bias_range is None
    cut = command_a_plus_lm_config(
        {**TINY, "num_experts": 2, "experts_held": [4, 2],
         "experts_routed": 16}, sequence_length=8)
    assert cut.num_experts == 16 and cut.moe_routing["experts_held"] == (4, 2)


@pytest.mark.parametrize("key, value, said", [
    ("layer_types", ["sliding_attention", "full_attention"] * 2,
     "three sliding_attention layers to one full_attention"),
    ("use_qk_norm", True, "use_qk_norm False"),
    ("attention_bias", True, "attention_bias False"),
    ("first_k_dense_replace", 1, "first_k_dense_replace 0"),
    ("shared_expert_combination_strategy", "sum",
     "shared_expert_combination_strategy 'average'"),
    ("expert_selection_fn", "softmax", "expert_selection_fn 'sigmoid'"),
    ("position_embedding_type", "rope", "position_embedding_type"),
    ("logit_scale", 0.25, "logit_scale 1"),
])
def test_the_builder_refuses_by_name_what_it_does_not_build(key, value, said):
    with pytest.raises(NotImplementedError, match=said):
        command_a_plus_lm_config({**TINY, key: value}, sequence_length=8)


def test_without_the_parallel_block_it_is_the_block_the_trunk_has():
    ff = FFModel(ff_config(1))
    build_transformer_lm(ff, command_a_plus_lm_config(
        {**TINY, "use_parallel_block": False}, sequence_length=8),
        batch_size=1)
    names = [l.name for l in ff.layers]
    assert "l0_ln2" in names and "l0_res1" in names and "l0_join" not in names


def test_the_parallel_block_is_a_fork_and_a_join(model):
    """One norm a layer feeds the attention and the expert layer alike, and
    their outputs join before the residual add: no ln2, no res1."""
    by_name = {l.name: l for l in model.layers}
    assert not any(n.endswith(("_ln2", "_res1")) for n in by_name)
    norm = by_name["l1_ln1"].outputs[0]
    assert all(t is norm for t in by_name["l1_attn"].inputs[:3])
    assert by_name["l1_moe"].inputs[0] is norm
    join = by_name["l1_join"]
    assert [t.owner_layer.name for t in join.inputs] == ["l1_attn", "l1_moe"]
    res = by_name["l1_res2"]
    assert [t.owner_layer.name for t in res.inputs] == ["l0_res2", "l1_join"]


def test_position_is_the_layer_kinds(model):
    fronts = {l.name: l for l in model.layers
              if l.op_type == OT.OP_MULTIHEAD_ATTENTION}
    swa, full = fronts["l0_attn"].params.front, fronts["l3_attn"].params.front
    assert fronts["l1_attn"].params.front == swa
    assert (swa.window, swa.rope_theta, swa.rope_interleaved) == (8, 5e4, True)
    assert (full.window, full.rope_theta) == (0, 0.0)
    # a layer without a theta takes no positions at all
    assert len(fronts["l0_attn"].inputs) == 4
    assert len(fronts["l3_attn"].inputs) == 3
    for f in (swa, full):
        assert (f.num_heads, f.kv_heads, f.head_dim) == (8, 2, 16)
        assert (f.q_width, f.kv_width, f.o_width) == (128, 32, 128)
    assert (swa.kind, full.kind) == ("swa", "gqa")
    assert (swa.attend_scope, full.attend_scope) == ("swa.attend",
                                                     "gqa.attend")


def test_the_weights_are_the_published_blocks(model):
    shapes = {n: {k: tuple(v.shape) for k, v in ws.items()}
              for n, ws in model._params.items()}
    assert shapes["l2_ln1"] == {"scale": (64,)}       # no bias
    assert shapes["ln_f"] == {"scale": (64,)}
    assert shapes["l2_attn"] == {"wq": (64, 128), "wk": (64, 32),
                                 "wv": (64, 32), "wo": (128, 64)}
    assert shapes["l2_moe"] == {
        "router": (64, 16), "gate": (16, 64, 24), "up": (16, 64, 24),
        "down": (16, 24, 64), "shared_gate": (64, 96),
        "shared_up": (64, 96), "shared_down": (96, 64)}  # no router_bias
    assert "lm_head" not in shapes and shapes["wte"] == {"kernel": (97, 64)}
    # the embedding is drawn around its mean
    table = np.asarray(model._params["wte"]["kernel"])
    assert abs(table.mean() - 0.3) < 0.02 and abs(table.std() - 0.1) < 0.02


def test_interleaved_rope_pairs_neighbouring_lanes():
    from flexflow_tpu.ops.attention import rope_half, rope_pairs

    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 8)), jnp.float32)
    angles = jnp.asarray(np.random.default_rng(1).normal(size=(3, 4)),
                         jnp.float32)
    got = np.asarray(rope_pairs(x, angles))
    assert error(got, np.asarray(ref.dsa.rope_interleaved(x, angles))) < 1e-6
    # the same rotation as the half form on lanes laid out its way
    order = np.r_[0:8:2, 1:8:2]
    assert error(got[:, order],
                 np.asarray(rope_half(x[:, order], angles))) < 1e-6


@pytest.mark.parametrize("layer", [0, 3])
def test_the_training_shaped_op_is_the_references_attention(model, layer):
    """One layer's op alone: the band and interleaved RoPE (layer 0), the
    whole past and no position (layer 3), 8 query heads on 2 KV heads."""
    node = model.layers[[l.name for l in model.layers].index(
        f"l{layer}_attn")]
    weights = {k: jnp.asarray(v, jnp.float32)
               for k, v in model._params[node.name].items()}
    x = jnp.asarray(np.random.default_rng(layer).normal(size=(2, SEQ, 64)),
                    jnp.float32)
    pos = jnp.tile(jnp.arange(SEQ, dtype=jnp.int32), (2, 1))
    d = ref.layer_dims(TINY, layer)
    ones = jnp.ones((64,), jnp.float32)
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            # the reference normalises first: undo it with a unit norm
            # over an input that is normalised already
            xb = ref.norm(x[b], ones, d.eps)
            ins = [xb[None]] * 3 + ([pos[:1]] if d.theta else [])
            (yb,), _ = get_op_def(OT.OP_MULTIHEAD_ATTENTION).forward(
                node.params, ins, weights, None,
                OpContext(training=False, mesh=None))
            want = ref.attention(x[b], weights, np.arange(SEQ), d,
                                 scale=ones, row_block=16)
            assert error(yb[0], np.asarray(want)) < TOL


def test_the_whole_lm_is_the_reference(model, tokens):
    logits = forward(model, tokens)
    for b in range(2):
        want, notes = ref.forward(getter(model), tokens[b], TINY)
        assert error(logits[b], want) < TOL
        assert len(notes) == 4 and all("gap" in note for note in notes)


@pytest.mark.parametrize("spoil", ref.SPOILS[1:])
def test_every_spoil_moves_the_references_logits(model, tokens, spoil):
    want, _ = ref.forward(getter(model), tokens[0], TINY)
    off, _ = ref.forward(getter(model), tokens[0], TINY, spoil=spoil)
    assert error(off, want) > 0.02, spoil


def test_the_reference_in_blocks_is_the_reference(model, tokens):
    """Row blocks change no number, in either kind of layer, and a layer's
    cache rows come out of the same forward."""
    whole, _ = ref.forward(getter(model), tokens[0], TINY, row_block=64)
    parts, notes = ref.forward(getter(model), tokens[0], TINY, row_block=8,
                               rows=[3, 17, SEQ - 1], cache_layer=3)
    assert error(parts, whole[[3, 17, SEQ - 1]]) < TOL
    k, v = notes[3]["cache"]
    assert k.shape == (SEQ, 32) and v.shape == (SEQ, 32)


def test_the_eight_shares_add_up_to_the_uncut_layer(model):
    """The share is tied to the model: an expert layer cut into 8 shares of
    2 experts (each told which it holds, routing over all 16) gives, the
    routed parts summed and the shared experts' average counted once, what
    the uncut reference gives for the whole layer."""
    from flexflow_tpu.ops.moe import MoEMLPParams

    w = {k: jnp.asarray(v, jnp.float32)
         for k, v in model._params["l1_moe"].items()}
    x = jnp.asarray(np.random.default_rng(5).normal(size=(12, 64)),
                    jnp.float32)
    r = ref.routing(TINY)
    none = jnp.full((12, r.k), -1, jnp.int32)
    uncut = {n: w[n] for n in ref.EXPERT_WEIGHTS}
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.expert_layer(x, uncut, none, 0.0, r=r, first=0)
        shared, _ = ref.expert_layer(x, uncut, none, 0.0, r=r, first=0,
                                     parts=("shared",))
        routed = []
        for first in range(0, 16, 2):
            held = {n: w[n][first:first + 2] if n in ("gate", "up", "down")
                    else w[n] for n in ref.EXPERT_WEIGHTS}
            part, _ = ref.expert_layer(x, held, none, 0.0, r=r, first=first,
                                       parts=("routed",))
            routed.append(np.asarray(part))
            # and the program's own share of the same experts, which
            # every chip computes with the shared experts beside it
            p = MoEMLPParams(16, 4, 24, scoring="sigmoid",
                             correction_bias=False, norm_topk_prob=True,
                             shared_intermediate_size=96, shared_scale=0.25,
                             experts_held=(first, 2))
            state = {k: jnp.zeros((), jnp.int32)
                     for k in ("assignments_total", "dropped_total")}
            (mine,), _ = get_op_def(OT.OP_MOE_MLP).forward(
                p, [x[None]], {**held, **state}, None,
                OpContext(training=False, mesh=None))
            assert error(mine[0], np.asarray(part + shared)) < TOL
    assert error(sum(routed) + np.asarray(shared), np.asarray(whole)) < TOL
    assert float(np.max(np.abs(routed[0]))) > 0
    assert float(np.max(np.abs(shared))) > 0


def test_the_published_widths_count_the_configurations_table():
    """Shapes only: the layers as the trunk builds them from the cell's
    configuration file, nothing allocated."""
    import json
    import math
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "configs", "command-a-plus-05-2026.json")
    with open(path) as f:
        config = json.load(f)
    ff = FFModel(ff_config(1))
    build_transformer_lm(ff, command_a_plus_lm_config(
        config, sequence_length=128), batch_size=1)
    count = {}
    for l in ff.layers:
        if l.shared_layer_guid >= 0:
            continue  # the tied head holds nothing of its own
        specs = get_op_def(l.op_type).weights(
            l.params, [t.dims for t in l.inputs])
        count[l.name] = sum(math.prod(ws.shape) for ws in specs
                            if ws.trainable)
    assert count["l0_attn"] == count["l3_attn"] == 142_606_336
    assert count["l0_ln1"] == 4096
    # the router, four shared experts, sixteen held experts
    assert count["l0_moe"] == 524_288 + 201_326_592 + 805_306_368
    assert count["wte"] == 32_768 * 4096 and "lm_head" not in count
    assert sum(count.values()) == 4 * 1_149_767_680 + 134_217_728 + 4096
    assert abs(sum(count.values()) - 4_733.3e6) < 0.05e6


# ------------------------------------------------------------- the tied head

def test_a_head_tied_to_an_embedding_reads_its_table():
    """FFModel.dense(shared_op=<an embedding>): one table, applied
    transposed, its gradient the sum of both uses."""
    ff = FFModel(ff_config(4))
    toks = ff.create_tensor((4, 6), DataType.DT_INT32, name="tokens")
    emb = ff.embedding(toks, 11, 8, name="emb")
    out = ff.dense(emb, 11, use_bias=False, name="head", shared_op=emb)
    head = out.owner_layer
    assert head.params.kernel_transposed and head.shared_layer_guid >= 0
    ff.compile(optimizer=SGDOptimizer(lr=0.1),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    assert set(ff._params) == {"emb"} and ff._weight_alias == {"head": "emb"}
    ids = np.random.default_rng(0).integers(0, 11, (4, 6)).astype(np.int32)
    table = np.asarray(ff._params["emb"]["kernel"])
    logits, _ = ff.executor.build_forward()(
        ff._params, ff._state, {"tokens": jnp.asarray(ids)}, False)
    assert error(logits, table[ids] @ table.T) < 1e-5
    ff.fit(ids, ids[..., None], epochs=1, shuffle=False)
    assert not np.allclose(np.asarray(ff._params["emb"]["kernel"]), table)
    # what cannot be tied still refuses by name
    with pytest.raises(ValueError, match="shared_op"):
        ff.dense(emb, 12, use_bias=False, shared_op=emb)


def test_the_tie_goes_through_compile_and_through_the_decode_graph(model):
    assert "lm_head" not in model._params
    assert model._weight_alias == {"lm_head": "wte"}
    eng = serve(model)
    dec = eng.decode_model
    assert "lm_head" not in dec._params
    head = next(l for l in dec.layers if l.name == "lm_head")
    wte = next(l for l in dec.layers if l.name == "wte")
    assert head.shared_layer_guid == wte.layer_guid
    assert head.op_type == OT.OP_LINEAR and wte.op_type == OT.OP_EMBEDDING
    # one table on the device, the compiled model's own
    assert dec._params["wte"]["kernel"] is model._params["wte"]["kernel"]


# ------------------------------------------------------------------- serving

def serve(ff, **kw):
    """The shared engine of these options (tests/small_lms.py), as new."""
    return small_lms.engine(ff, **{**dict(
        slots=3, max_seq_len=SEQ, prefill_chunk=8, kv_block_size=4,
        kv_num_blocks=48), **kw})


def is_greedy(ff, prompt, reply) -> bool:
    """Whether `reply` is the reference's greedy continuation of `prompt`:
    one forward over both (a row's logits depend on no later token), each
    reply token the argmax of the row before it."""
    logits, _ = ref.forward(getter(ff), [*prompt, *reply[:-1]], TINY)
    return np.argmax(logits[len(prompt) - 1:], axis=-1).tolist() == reply


@pytest.mark.parametrize("length, split", [
    (7, 4),     # the whole context under the window of 8
    (14, 6),    # prefilled under it, crossing it while it decodes
    (SEQ, 30),  # far past it: five windows, ten blocks
    (SEQ, 9),
])
def test_prefill_in_chunks_then_decode_is_the_references_forward(
        model, length, split):
    """Every row's logits through the two-group cache, prefilled in chunks
    of 8 to `split` and decoded from there, against the reference's full
    forward."""
    seq = np.random.default_rng(length).integers(0, 97, length).tolist()
    want, _ = ref.forward(getter(model), seq, TINY)
    got = decode_graph_logits(serve(model), seq, split)
    assert error(got, want) < TOL


def test_the_window_group_needs_only_the_blocks_of_the_window(model):
    """The same, with the window group's table holding, at every step,
    only the blocks from the one of row `position - 7` on: under the
    window that is every block, past it what fell behind points at the
    scratch block and changes no logit; with one block too few the logits
    are off."""
    seq = np.random.default_rng(3).integers(0, 97, SEQ).tolist()
    want, _ = ref.forward(getter(model), seq, TINY)
    eng = serve(model)
    first = eng.block_manager.window.first_block
    assert first(7) == 0 and first(11) == 1
    got = decode_graph_logits(
        eng, seq, 6, held=lambda at: set(range(first(at), 10)))
    assert error(got, want) < TOL
    lost = decode_graph_logits(
        serve(model), seq, 6,
        held=lambda at: set(range(first(at) + 1, 10)))
    assert error(lost, want) > 1e-3


def test_sessions_shorter_and_longer_than_the_window_in_one_queue(
        model, monkeypatch):
    """The engine's own admission, freeing and prefix match over both
    groups: prompts of 5 (its context crosses the window of 8 while it
    decodes), 21 and 30 tokens, the greedy continuation of the reference
    each; nothing is given back before row 8, then a block every 4 rows;
    the step's span and stats() say so."""
    from flexflow_tpu import telemetry

    seen = []
    real = telemetry.span

    def span(name, **args):
        if name in ("serve.step", "serve.prefill"):
            seen.append((name, args))
        return real(name, **args)

    monkeypatch.setattr(telemetry, "span", span)
    rng = np.random.default_rng(1)
    eng = serve(model)
    short = rng.integers(0, 97, 5).tolist()
    assert is_greedy(model, short,
                     eng.generate([short], max_new_tokens=10)[0])
    steps = [a for n, a in seen if n == "serve.step"]
    # decode rows at positions 5 .. 13: contexts of 6, 7, 8 rows are inside
    # the window, and the first block is given back when the row at
    # position 11 no longer reads row 3
    assert [s["under_window"] for s in steps] == [1, 1, 1, 0, 0, 0, 0, 0, 0]
    assert [s["window_rows"] for s in steps] == [6, 7, 8, 8, 8, 8, 8, 8, 8]
    assert [s["window_blocks_freed"] for s in steps] == [
        0, 0, 0, 0, 0, 0, 0, 1, 1]
    st = eng.stats()
    assert (st["under_window"], st["window_blocks_freed"]) == (3, 1)
    prompts = [rng.integers(0, 97, n).tolist() for n in (21, 30)]
    for p, o in zip(prompts, eng.generate(prompts, max_new_tokens=8)):
        assert len(o) == 8 and is_greedy(model, p, o)
    eng.block_manager.window.check_invariants()
    st = eng.stats()
    assert st["under_window"] == 3 and st["window_blocks_freed"] > 1
    # a history under the window is pinned whole in the window group, one
    # past it by the blocks of its last rows: a follow-up turn on each
    # finds all of it over both groups
    for history in (short, prompts[0]):
        follow = history + [5, 6, 7]
        out = eng.generate([follow], max_new_tokens=4)
        assert len(out[0]) == 4 and is_greedy(model, follow, out[0])
        assert eng.scheduler.completed[-1].matched_prefix_len == len(history)
    eng.block_manager.window.check_invariants()


def test_the_scopes_name_the_projections_by_kind(model):
    eng = serve(model)
    dec, slots = eng.decode_model, eng.spec.slots
    xs = eng._stage_inputs(np.zeros((slots, 1), np.int32),
                           np.full((slots, 1), SEQ, np.int32))
    text = eng._step_fn.lower(
        dec._params, dec._state, xs, jnp.zeros((slots,), jnp.int32),
        jax.random.key(0), jnp.zeros((slots,), jnp.float32)).as_text(
            debug_info=True)
    for scope in ("l0_attn/swa.qkv", "l0_attn/swa.attend", "l0_attn/swa.out",
                  "l3_attn/gqa.qkv", "l3_attn/gqa.attend", "l3_attn/gqa.out",
                  "l2_moe/moe.shared"):
        assert scope in text, scope
    assert "l3_attn/swa." not in text and "l0_attn/gqa." not in text


def test_the_defaults_leave_every_other_block_as_it_was():
    c = TransformerLMConfig()
    assert (c.parallel_block, c.norm_bias, c.tie_embeddings,
            c.embedding_mean) == (False, True, False, 0.0)
    from flexflow_tpu.ops import LayerNormParams, LinearParams, MoEMLPParams
    from flexflow_tpu.ops.attention import AttentionFrontEnd

    assert LayerNormParams((2,)).bias and not LinearParams(
        4).kernel_transposed
    p = MoEMLPParams(8, 2, 16)
    assert (p.shared_scale, p.correction_bias) == (1.0, True)
    assert not AttentionFrontEnd(64, 4).rope_interleaved
