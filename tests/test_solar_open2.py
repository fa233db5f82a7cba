"""Solar-Open2 through the normal path at a small size (PR 33): the delta
rule's kernel and its chunk form, grouped-KV gated attention without
positions, the 1 : 3 layer pattern through `serve()` with per-slot
recurrent state beside the paged pool, each against the float32 reference
(models/solar_open2_reference.py) on seeded weights.
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
from flexflow_tpu.kernels import delta_rule as dr
from flexflow_tpu.models import (
    build_transformer_lm, solar_open2_lm_config, solar_open2_reference as ref,
)
import small_lms

# hidden 64; softmax layers 4 query heads over 2 KV heads of 16, gated, no
# positions; delta-rule layers 4 heads of 16; 16 experts of 24, 4 a token,
# one shared; layers 0 and 4 of 5 are softmax ones: the pattern 1 : 3
TINY = dict(
    model_type="solar_open2", hidden_size=64, num_hidden_layers=5,
    num_attention_heads=4, head_dim=16, num_key_value_heads=2,
    linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                        "num_heads": 4, "num_kv_heads": None},
    vocab_size=97, moe_intermediate_size=24, rms_norm_eps=1e-5,
    first_k_dense_replace=0, use_rope=False, gqa_layers=[0, 4],
    use_gqa_gate=True, kda_use_full_proj=False, kda_allow_neg_eigval=True,
    n_routed_experts=16, n_shared_experts=1, norm_topk_prob=True,
    routed_scaling_factor=1, num_experts_per_tok=4)
SEQ = 40
# float32 against float32, as a share of the largest logit: the program's
# sums run in another order than the reference's (a scan a token against a
# scan a token, but fused otherwise), nothing else differs
TOL = 2e-5


def build(config=TINY, seq=SEQ, batch=2, flags=()):
    argv = sys.argv
    sys.argv = ["t", "-b", str(batch), "--mesh", "1,1,1,1",
                "--no-verify-plan", *flags]
    try:
        cfg = FFConfig()
    finally:
        sys.argv = argv
    ff = FFModel(cfg)
    build_transformer_lm(ff, solar_open2_lm_config(
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


def getter(ff):
    return lambda node, weight: ff._params[node][weight]


def error(got, want):
    return float(np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(want)))


SERVE = dict(slots=3, max_seq_len=SEQ, prefill_chunk=8, kv_block_size=4,
             kv_num_blocks=40)


def serve(ff, **kw):
    """The shared engine of these options (tests/small_lms.py), as new."""
    return small_lms.engine(ff, **{**SERVE, **kw})


def prompts(n, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 97, int(l)).tolist() for l in lengths[:n]]


def is_greedy(ff, prompt, reply, new) -> bool:
    """Whether `reply` is the reference's own greedy continuation of
    `prompt` by `new` tokens: one forward over both (padded to one length:
    causal, a row's logits depend on no later token; one compile), each
    reply token the argmax of the row before it."""
    padded = np.zeros((SEQ,), np.int32)
    padded[:len(prompt) + new - 1] = [*prompt, *reply[:-1]]
    logits, _ = ref.forward(
        getter(ff), padded, TINY,
        rows=range(len(prompt) - 1, len(prompt) + new - 1))
    return np.argmax(logits, axis=-1).tolist() == reply


# ------------------------------------------------------------------ the rule

def operands(rng, rows, tokens, heads, d):
    q, k, v = (jnp.asarray(rng.normal(size=(rows, tokens, heads, d)),
                           jnp.float32) for _ in range(3))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    alpha = jnp.asarray(rng.uniform(0.5, 1, (rows, tokens, heads, d)),
                        jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 2, (rows, tokens, heads)), jnp.float32)
    return q, k, v, alpha, beta


@pytest.mark.parametrize("update", [dr.delta_rule_reference,
                                    dr.delta_rule_update],
                         ids=["scan", "kernel"])
def test_tokens_one_by_one_and_in_unequal_chunks_give_the_scan(update):
    """A sequence of 23 tokens run whole (the reference's scan), a token a
    call, and in chunks of 7, 1, 10 and 5 with the state carried across:
    the same outputs and the same final state."""
    rng = np.random.default_rng(1)
    heads, d, n = 8, 8, 23
    q, k, v, alpha, beta = operands(rng, 1, n, heads, d)
    live, keep = jnp.ones((1, n), bool), jnp.ones((1,), bool)
    zero = jnp.zeros((1, heads, d, d), jnp.float32)
    whole, last = dr.delta_rule_reference(zero, q, k, v, alpha, beta, live,
                                          keep)
    for cuts in ([1] * n, [7, 1, 10, 5]):
        state, outs, at = zero, [], 0
        for c in cuts:
            s = slice(at, at + c)
            o, state = update(state, q[:, s], k[:, s], v[:, s], alpha[:, s],
                              beta[:, s], live[:, s], keep)
            outs.append(o)
            at += c
        assert error(jnp.concatenate(outs, 1), np.asarray(whole)) < 1e-5
        assert error(state, np.asarray(last)) < 1e-5


def test_the_kernel_interpreted_gives_its_reference():
    """Rows of several tokens with a dead tail, a dead row and a row that
    starts from nothing: outputs and states of the Pallas kernel (the
    interpreter runs it here) against the jnp reference; the dead row's
    state is bitwise what it was."""
    rng = np.random.default_rng(2)
    rows, tokens, heads, d = 4, 5, 16, 8
    q, k, v, alpha, beta = operands(rng, rows, tokens, heads, d)
    state = jnp.asarray(rng.normal(size=(rows, heads, d, d)), jnp.float32)
    live = jnp.asarray([[1, 1, 1, 0, 0], [1] * 5, [0] * 5, [1, 0, 0, 0, 0]],
                       bool)
    keep = jnp.asarray([True, False, True, True])
    assert dr.delta_rule_gate(heads, d, interpret=True) is None
    assert dr.delta_rule_gate(heads, 64, interpret=False) is not None
    o_ref, s_ref = dr.delta_rule_reference(state, q, k, v, alpha, beta, live,
                                           keep)
    o, s = dr.delta_rule_update(state, q, k, v, alpha, beta, live, keep)
    assert error(o, np.asarray(o_ref)) < 1e-5
    assert error(s, np.asarray(s_ref)) < 1e-5
    assert np.array_equal(np.asarray(s[2]), np.asarray(state[2]))
    assert not np.asarray(o[0, 3:]).any() and not np.asarray(o[2]).any()
    # a row that starts from nothing forgets what its slot held
    fresh = dr.delta_rule_reference(jnp.zeros_like(state), q, k, v, alpha,
                                    beta, live, keep)[1]
    assert error(s[1], np.asarray(fresh[1])) < 1e-5


# ----------------------------------------------------- grouped-KV attention

@pytest.mark.parametrize("gate", [True, False])
def test_grouped_gated_attention_without_positions_training_shaped(gate):
    """OP_MULTIHEAD_ATTENTION with 2 KV heads under 4 query heads of 16 in
    a hidden size of 48 (head_dim apart from hidden / heads), with and
    without the output gate, against the reference's layer."""
    from flexflow_tpu.ops import MultiHeadAttentionParams
    from flexflow_tpu.ops.attention import AttentionFrontEnd
    from flexflow_tpu.ops.base import OpContext, get_op_def

    rng = np.random.default_rng(3)
    front = AttentionFrontEnd(48, 4, use_bias=False, num_kv_heads=2,
                              head_size=16, output_gate=gate)
    p = MultiHeadAttentionParams(front, causal=True)
    specs = get_op_def(OT.OP_MULTIHEAD_ATTENTION).weights(
        p, [(2, 12, 48)] * 3)
    assert {s.name: s.shape for s in specs} == {
        "wq": (48, 64), "wk": (48, 32), "wv": (48, 32), "wo": (64, 48),
        **({"wg": (48, 64)} if gate else {})}
    w = {s.name: jnp.asarray(0.2 * rng.normal(size=s.shape), jnp.float32)
         for s in specs}
    x = jnp.asarray(rng.normal(size=(2, 12, 48)), jnp.float32)
    (y,), _ = get_op_def(OT.OP_MULTIHEAD_ATTENTION).forward(
        p, [x, x, x], w, None, OpContext(training=False, mesh=None))
    cfg = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               use_gqa_gate=gate)
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            assert error(y[b], np.asarray(
                ref.gqa_attention(x[b], w, cfg))) < TOL


def test_the_grouped_paged_decode_kernel_gives_its_reference():
    """8 query heads over 2 KV heads of 128 (the pool's row is 256 wide),
    rows of unlike lengths, one dead: the Pallas kernel (interpreted)
    against the gather-and-einsum reference."""
    import importlib

    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")

    rng = np.random.default_rng(4)
    rows, heads, kv, d, bs, width = 5, 8, 2, 128, 8, 18
    q = jnp.asarray(rng.normal(size=(rows, 1, heads * d)), jnp.float32)
    pool_k, pool_v = (jnp.asarray(rng.normal(size=(1 + rows * width, bs,
                                                   kv * d)), jnp.float32)
                      for _ in range(2))
    table = jnp.asarray(1 + rng.permutation(rows * width).reshape(
        rows, width), jnp.int32)
    lengths = jnp.asarray([1, 9, 0, 144, 77], jnp.int32)
    assert fa.paged_decode_gate(width * bs, bs, kv * d, kv, 4, True) is None
    got = fa.paged_flash_decode_attention(
        q, pool_k, pool_v, table, lengths, num_heads=heads, num_kv_heads=kv)
    want = fa.paged_decode_attention_reference(
        q, pool_k, pool_v, table, (lengths - 1)[:, None], num_heads=heads,
        num_kv_heads=kv)
    live = np.asarray(lengths) > 0
    assert error(np.asarray(got)[live], np.asarray(want)[live]) < 1e-5


# ------------------------------------------------------------------ the model

def test_the_config_builder_reads_the_published_keys():
    c = solar_open2_lm_config(TINY, sequence_length=8)
    assert c.layer_pattern == ("mha", "delta", "delta", "delta", "mha")
    assert (c.position, c.num_kv_heads, c.head_dim, c.attention_gate) == (
        "none", 2, 16, True)
    assert c.moe_routing["norm_topk_prob"] and c.delta.conv_kernel == 4
    with pytest.raises(ValueError, match="layer_pattern"):
        solar_open2_lm_config(dict(TINY, gqa_layers=[0]), sequence_length=8
                              ).__class__(num_layers=2, layer_pattern=("x",))


def test_training_shaped_graph_gives_the_references_logits(model):
    assert "wpe" not in model._params        # no position enters anywhere
    tokens = np.random.default_rng(0).integers(0, 97, (2, SEQ)).astype(
        np.int32)
    pos = np.tile(np.arange(SEQ, dtype=np.int32), (2, 1))
    logits, _ = model.executor.build_forward()(
        model._params, model._state,
        {"tokens": jnp.asarray(tokens), "positions": jnp.asarray(pos)}, False)
    for b in range(2):
        want, _ = ref.forward(getter(model), tokens[b], TINY)
        assert error(logits[b], want) < TOL


def decode_logits(engine, prompt, chunks, decoded):
    """The decode graph's logits, driven as the engine drives it: the
    prompt in `chunks` (unequal) through slot 1 of the rectangle, then
    `decoded` tokens of the reference's choosing one a call; rows
    (len(chunks) + decoded, vocabulary): each call's last live row."""
    dec, ex = engine.decode_model, engine.decode_model.executor
    slots = engine.spec.slots
    table = np.zeros((slots, engine.block_manager.table_width), np.int32)
    table[1] = 1 + np.arange(table.shape[1])

    apply = jax.jit(lambda params, state, xs: ex._apply(
        params, state, ex._cast_compute(xs), training=False, rng=None)[:2])

    def call(tokens, at):
        n = len(tokens)
        toks = np.zeros((slots, n), np.int32)
        pos = np.full((slots, n), engine.max_seq_len, np.int32)
        toks[1], pos[1] = tokens, np.arange(at, at + n)
        xs = engine._stage_inputs(toks, pos)
        xs["page_table"] = jax.device_put(table, xs["page_table"].sharding)
        logits, dec._state = apply(dec._params, dec._state, xs)
        return np.asarray(logits[1, n - 1])

    rows, at = [], 0
    for c in chunks:
        rows.append(call(prompt[at:at + c], at))
        at += c
    return rows, call


def test_prefill_in_chunks_then_decode_gives_the_references_logits(model):
    """Through serve()'s decode graph: a prompt of 19 in chunks of 8, 8
    and 3, then 6 decoded tokens; every call's last row against the
    reference's full forward over the whole sequence."""
    engine = serve(model)
    prompt = prompts(1, [19])[0]
    rows, call = decode_logits(engine, prompt, [8, 8, 3], 6)
    seq = list(prompt)
    for _ in range(6):
        seq.append(int(np.argmax(rows[-1])))
        rows.append(call(seq[-1:], len(seq) - 1))
    want, _ = ref.forward(getter(model), np.asarray(seq), TINY)
    at = [7, 15, *range(18, 25)]
    assert error(np.stack(rows), want[at]) < TOL


def test_serve_decodes_what_the_reference_decodes(model):
    engine = serve(model)
    st = engine.stats()
    assert st["state_slots"] == 3 and st["state_resets"] == 0
    # three delta-rule layers: 4 heads x 16 x 16 float32 and 3 x 192 a slot
    assert st["state_bytes"] == 3 * 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    for prompt in prompts(2, [19, 5]):
        (reply,) = engine.generate([prompt], max_new_tokens=6)
        assert is_greedy(model, prompt, reply, 6)
    assert engine.stats()["state_resets"] == 2
    assert not engine.spec.prefix_cache and not engine.spec.prefix_sharing


def test_an_interleaved_batch_equals_each_request_alone(model):
    """Five requests over three slots, prompts of unlike lengths: slots
    are reused while others decode, chunks ride beside decoding rows, and
    every stream is what the request gives alone (in a fresh engine, and
    by the reference's own greedy decoding)."""
    ps = prompts(5, [19, 3, 11, 26, 8], seed=7)
    together = serve(model).generate(ps, max_new_tokens=7)
    assert together[3] == serve(model).generate([ps[3]], max_new_tokens=7)[0]
    for p, got in zip(ps, together):
        assert is_greedy(model, p, got, 7)


def test_a_reused_slot_starts_from_nothing(model):
    """One slot, a step in flight: the second request runs in the slot
    the first left its state in, and gives what a fresh engine gives."""
    a, b = prompts(2, [17, 9], seed=11)
    engine = serve(model, slots=1, kv_num_blocks=12)
    first = engine.submit(a, max_new_tokens=5)
    second = engine.submit(b, max_new_tokens=5)
    engine.run_until_drained()
    assert engine.stats()["steps_ahead"] > 0
    assert engine.stats()["state_resets"] == 2
    # serve(): a new engine, whose slot has held nothing
    fresh = model.serve(**{**SERVE, "slots": 1, "kv_num_blocks": 12})
    assert second.generated == fresh.generate([b], max_new_tokens=5)[0]
    assert is_greedy(model, a, first.generated, 5)


def test_a_chunk_as_rows_equals_the_rectangle():
    """Where the paged kernel serves rows (interpreted here: head_dim 128,
    a cache of 128 rows), a chunk rides as single-query rows past the
    slots and the delta-rule layers run them in order from the chunk's
    slot's state: the same streams as the rectangle's."""
    big = dict(TINY, hidden_size=32, num_attention_heads=2, head_dim=128,
               num_key_value_heads=1, num_hidden_layers=2, gqa_layers=[0],
               linear_attn_config={"short_conv_kernel_size": 4,
                                   "head_dim": 8, "num_heads": 8,
                                   "num_kv_heads": None},
               n_routed_experts=8, num_experts_per_tok=2)
    ff = build(big, seq=128, batch=1)
    ps = prompts(3, [13, 21, 6], seed=5)
    kw = dict(slots=2, max_seq_len=128, prefill_chunk=8, kv_block_size=16,
              kv_num_blocks=40)
    # serve(), twice: the model is this test's alone
    rows = ff.serve(impl="flash", **kw)
    assert rows._chunk_rows
    got = rows.generate(ps, max_new_tokens=4)
    assert rows.stats()["row_steps"] > 0
    rect = ff.serve(impl="xla", **kw)
    assert not rect._chunk_rows
    assert got == rect.generate(ps, max_new_tokens=4)


@pytest.mark.parametrize("how", ["prefix_cache", "prefix_sharing",
                                 "speculate", "disaggregate", "extract_kv",
                                 "admit_prefilled"])
def test_what_recurrent_state_cannot_follow_is_refused(model, how):
    """A matched prefix, a rewound cursor and the KV handoff are sound for
    attention only: a graph with recurrent layers is refused by name."""
    if how in ("prefix_cache", "prefix_sharing"):
        with pytest.raises(ValueError, match="recurrent layers"):
            serve(model, **{how: True})
    elif how == "speculate":
        with pytest.raises(NotImplementedError, match="recurrent layers"):
            serve(model, speculate=True, draft_model=model)
    elif how == "disaggregate":
        with pytest.raises(NotImplementedError, match="recurrent layers"):
            serve(model, disaggregate=True)
    else:
        engine = serve(model)
        with pytest.raises(NotImplementedError, match="recurrent layers"):
            if how == "extract_kv":
                engine.extract_kv(0, 4)
            else:
                engine.admit_prefilled(None, 0, None, None)
