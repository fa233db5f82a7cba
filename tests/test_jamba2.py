"""Jamba2 through the normal path at a small size (PR 55): the selective
scan's kernel and its chunk form, the state-space layer training-shaped
and through `serve()` with per-slot recurrent state beside the paged pool
of the multi-query softmax layers, each against the float32 reference
(models/jamba2_reference.py) on seeded weights.
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
from flexflow_tpu.kernels import selective_scan as ss
from flexflow_tpu.models import (
    build_transformer_lm, jamba2_reference as ref, jamba_lm_config,
)

# the same vocabulary of 97 and the same drive of the decode graph
import small_lms
from test_solar_open2 import decode_logits, error, getter, prompts

# hidden 64, inner 128, state 16, dt rank 8, 4 taps; 4 query heads over 1
# KV head of 16; two periods of mamba, mamba, attention, mamba
TINY = dict(
    model_type="jamba", hidden_size=64, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=1, intermediate_size=96,
    vocab_size=97, rms_norm_eps=1e-6, attn_layer_period=4,
    attn_layer_offset=2, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
    mamba_dt_rank=8, mamba_conv_bias=True, mamba_proj_bias=False,
    num_experts=1, num_experts_per_tok=1, tie_word_embeddings=True,
    hidden_act="silu")
SEQ = 40
# float32 against float32, as a share of the largest logit: the program's
# sums run in another order than the reference's, nothing else differs
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
    build_transformer_lm(ff, jamba_lm_config(
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


SERVE = dict(slots=3, max_seq_len=SEQ, prefill_chunk=8, kv_block_size=4,
             kv_num_blocks=40)


def serve(ff, **kw):
    """The shared engine of these options (tests/small_lms.py), as new."""
    return small_lms.engine(ff, **{**SERVE, **kw})


def is_greedy(ff, prompt, reply, new, config=TINY, seq=SEQ) -> bool:
    """Whether `reply` is the reference's own greedy continuation of
    `prompt` by `new` tokens: one forward over both (padded to one length:
    causal, a row's logits depend on no later token; one compile), each
    reply token the argmax of the row before it."""
    padded = np.zeros((seq,), np.int32)
    padded[:len(prompt) + new - 1] = [*prompt, *reply[:-1]]
    logits, _ = ref.forward(
        getter(ff), padded, config,
        rows=range(len(prompt) - 1, len(prompt) + new - 1))
    return np.argmax(logits, axis=-1).tolist() == reply


# ------------------------------------------------------------------ the scan

def operands(rng, rows, tokens, n=16, channels=128):
    f = jnp.float32
    return dict(
        dt=jnp.asarray(rng.uniform(0.001, 0.1, (rows, tokens, channels)), f),
        c=jnp.asarray(rng.normal(size=(rows, tokens, channels)), f),
        B=jnp.asarray(rng.normal(size=(rows, tokens, n)), f),
        C=jnp.asarray(rng.normal(size=(rows, tokens, n)), f),
        A=-jnp.exp(jnp.asarray(rng.uniform(0, 2.77, (n, channels)), f)),
        D=jnp.asarray(rng.normal(size=(channels,)), f))


def scan(update, state, o, live, keep, at=slice(None)):
    return update(state, o["dt"][:, at], o["c"][:, at], o["B"][:, at],
                  o["C"][:, at], o["A"], o["D"], live[:, at], keep)


@pytest.mark.parametrize("update", [ss.selective_scan_reference,
                                    ss.selective_scan_update],
                         ids=["scan", "kernel"])
def test_tokens_one_by_one_and_in_unequal_chunks_give_the_scan(update):
    """A sequence of 24 tokens run whole (the reference's scan), a token a
    call, and in chunks of 8, 1, 10 and 5 with the state carried across
    (8 goes eight tokens a program): the same outputs and final state."""
    rng = np.random.default_rng(1)
    n = 24
    o = operands(rng, 1, n)
    live, keep = jnp.ones((1, n), bool), jnp.ones((1,), bool)
    zero = jnp.zeros((1, 16, 128), jnp.float32)
    whole, last = scan(ss.selective_scan_reference, zero, o, live, keep)
    for cuts in ([1] * n, [8, 1, 10, 5]):
        state, outs, at = zero, [], 0
        for c in cuts:
            y, state = scan(update, state, o, live, keep, slice(at, at + c))
            outs.append(y)
            at += c
        assert error(jnp.concatenate(outs, 1), np.asarray(whole)) < 1e-5
        assert error(state, np.asarray(last)) < 1e-5


@pytest.mark.parametrize("rows,tokens", [(8, 1), (1, 16), (4, 5), (16, 3)],
                         ids=["rows", "chunk", "rectangle", "row-blocks"])
def test_the_kernel_interpreted_gives_its_reference(rows, tokens):
    """One token a row (eight rows a program), one row of a chunk (eight
    tokens a program) and rectangles, with dead tails, a dead row and a
    row that starts from nothing: outputs and states of the Pallas kernel
    (the interpreter runs it here) against the jnp scan; a dead row's
    state is bitwise what it was."""
    rng = np.random.default_rng(2)
    o = operands(rng, rows, tokens)
    state = jnp.asarray(rng.normal(size=(rows, 16, 128)), jnp.float32)
    n_live = rng.integers(1, tokens + 1, rows)
    if rows > 1:
        n_live[rows // 2] = 0                   # a dead row
    live = jnp.asarray(np.arange(tokens)[None] < n_live[:, None])
    keep = jnp.asarray(np.arange(rows) != rows - 1)   # the last starts anew
    assert ss.selective_scan_gate(rows, 16, 128, True) is None
    want_y, want_s = scan(ss.selective_scan_reference, state, o, live, keep)
    y, s = scan(ss.selective_scan_update, state, o, live, keep)
    assert error(y, np.asarray(want_y)) < 1e-5
    assert error(s, np.asarray(want_s)) < 1e-5
    if rows > 1:
        dead = rows // 2
        assert np.array_equal(np.asarray(s[dead]), np.asarray(state[dead]))
        assert not np.asarray(y[dead]).any()
        # a row that starts from nothing forgets what its slot held
        fresh = scan(ss.selective_scan_reference, jnp.zeros_like(state), o,
                     live, keep)[1]
        assert error(s[-1], np.asarray(fresh[-1])) < 1e-5


def test_the_gate_says_why_a_shape_is_declined():
    assert "state size" in ss.selective_scan_gate(8, 12, 128, False)
    assert "channels" in ss.selective_scan_gate(8, 16, 96, False)
    assert "rows" in ss.selective_scan_gate(12, 16, 128, False)
    assert ss.selective_scan_gate(256, 16, 5120, False) is None
    assert ss.selective_scan_gate(1, 16, 5120, False) is None
    assert ss._blocks(256, 1) == (8, 1) and ss._blocks(1, 512) == (1, 8)
    assert ss._channel_block(5120) == 2560 and ss._channel_block(128) == 128


# ----------------------------------------------------------------- the layer

def layer_weights(rng, front, in_dim, scale=0.3):
    specs = front.weight_specs(in_dim)
    w = {s.name: jnp.asarray(scale * rng.normal(size=s.shape), jnp.float32)
         for s in specs}
    w["dt_bias"] = jnp.asarray(rng.uniform(-6.9, -2.25, w["dt_bias"].shape),
                               jnp.float32)
    w["a_log"] = jnp.asarray(rng.uniform(0, 2.77, w["a_log"].shape),
                             jnp.float32)
    return w


def test_the_training_shaped_op_gives_the_references_layer():
    """OP_SELECTIVE_SSM on (2, 12, 48), inner 96, state 16, dt rank 6,
    against the reference's layer; the declared weights are the published
    layer's."""
    from flexflow_tpu.ops.base import OpContext, get_op_def
    from flexflow_tpu.ops.ssm import MambaFrontEnd, SelectiveSSMParams

    rng = np.random.default_rng(3)
    front = MambaFrontEnd(48, 96, 16, 6)
    op = get_op_def(OT.OP_SELECTIVE_SSM)
    specs = op.weights(SelectiveSSMParams(front), [(2, 12, 48)])
    assert {s.name: s.shape for s in specs} == {
        "w_in": (48, 192), "conv": (4, 96), "conv_bias": (96,),
        "w_x": (96, 38), "w_dt": (6, 96), "dt_bias": (96,),
        "a_log": (16, 96), "d": (96,), "w_out": (96, 48),
        "dt_norm": (6,), "b_norm": (16,), "c_norm": (16,)}
    w = layer_weights(rng, front, 48)
    x = jnp.asarray(rng.normal(size=(2, 12, 48)), jnp.float32)
    (y,), _ = op.forward(SelectiveSSMParams(front), [x], w, None,
                         OpContext(training=False, mesh=None))
    cfg = dict(hidden_size=48, mamba_expand=2, mamba_d_state=16,
               mamba_d_conv=4, mamba_dt_rank=6, mamba_conv_bias=True,
               rms_norm_eps=1e-6)
    with jax.default_matmul_precision("highest"):
        for b in range(2):
            want, _, _ = ref.mamba_layer(x[b], w, cfg)
            assert error(y[b], np.asarray(want)) < TOL


def test_the_gradient_is_autodiffs_of_the_reference():
    """d loss / d (x, every weight) of the training-shaped op against
    jax.grad of the reference's layer: the scan is differentiable."""
    from flexflow_tpu.ops.base import OpContext, get_op_def
    from flexflow_tpu.ops.ssm import MambaFrontEnd, SelectiveSSMParams

    rng = np.random.default_rng(4)
    p = SelectiveSSMParams(MambaFrontEnd(32, 64, 16, 4))
    w = layer_weights(rng, p.front, 32)
    x = jnp.asarray(rng.normal(size=(1, 9, 32)), jnp.float32)
    probe = jnp.asarray(rng.normal(size=(9, 32)), jnp.float32)
    cfg = dict(hidden_size=32, mamba_expand=2, mamba_d_state=16,
               mamba_d_conv=4, mamba_dt_rank=4, mamba_conv_bias=True,
               rms_norm_eps=1e-6)

    def program(x, w):
        (y,), _ = get_op_def(OT.OP_SELECTIVE_SSM).forward(
            p, [x], w, None, OpContext(training=True, mesh=None))
        return jnp.sum(y[0] * probe)

    def reference(x, w):
        return jnp.sum(ref.mamba_layer(x[0], w, cfg)[0] * probe)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(program, argnums=(0, 1))(x, w)
        want = jax.grad(reference, argnums=(0, 1))(x, w)
    assert error(got[0], np.asarray(want[0])) < 1e-4
    for name in w:
        assert error(got[1][name], np.asarray(want[1][name])) < 1e-4, name


def test_the_initializers_are_the_published_layers():
    from flexflow_tpu.ops.ssm import MambaFrontEnd

    inits = MambaFrontEnd(64, 128, 16, 8).initializers()
    key = jax.random.key(0)
    a_log = np.asarray(inits["a_log"](key, (16, 128), jnp.float32))
    assert np.allclose(np.exp(a_log), np.arange(1, 17)[:, None])
    dt = np.log1p(np.exp(np.asarray(
        inits["dt_bias"](key, (4096,), jnp.float32))))
    assert 0.001 <= dt.min() and dt.max() <= 0.1
    for name in ("conv", "conv_bias"):
        taps = np.asarray(inits[name](key, (4, 1024), jnp.float32))
        assert -0.5 <= taps.min() < -0.4 and 0.4 < taps.max() <= 0.5


# ------------------------------------------------------------------ the model

def test_the_config_builder_reads_the_published_keys():
    c = jamba_lm_config(TINY, sequence_length=8)
    assert c.layer_pattern == ("mamba", "mamba", "mha", "mamba") * 2
    assert (c.position, c.num_kv_heads, c.head_dim, c.tie_embeddings) == (
        "none", 1, 16, True)
    m = c.mamba
    assert (m.inner, m.state_size, m.dt_rank, m.conv_kernel, m.conv_bias) == (
        128, 16, 8, 4, True)
    with pytest.raises(NotImplementedError, match="num_experts"):
        jamba_lm_config(dict(TINY, num_experts=16), sequence_length=8)
    with pytest.raises(ValueError, match="MambaFrontEnd"):
        c.__class__(num_layers=1, layer_pattern=("mamba",))


def test_training_shaped_graph_gives_the_references_logits(model):
    assert "wpe" not in model._params        # no position enters anywhere
    assert "lm_head" not in model._params    # the head is the embedding's
    tokens = np.random.default_rng(0).integers(0, 97, (2, SEQ)).astype(
        np.int32)
    pos = np.tile(np.arange(SEQ, dtype=np.int32), (2, 1))
    logits, _ = model.executor.build_forward()(
        model._params, model._state,
        {"tokens": jnp.asarray(tokens), "positions": jnp.asarray(pos)}, False)
    for b in range(2):
        want, _ = ref.forward(getter(model), tokens[b], TINY)
        assert error(logits[b], want) < TOL


def test_prefill_in_chunks_then_decode_gives_the_references_logits(model):
    """Through serve()'s decode graph: a prompt of 19 in chunks of 8, 8
    and 3, then 6 decoded tokens; every call's last row against the
    reference's full forward over the whole sequence, and the slot's h and
    convolution tail against the reference's after the last token."""
    # serve(): a new engine, whose other slots no call has touched
    engine = model.serve(**SERVE)
    prompt = prompts(1, [19])[0]
    rows, call = decode_logits(engine, prompt, [8, 8, 3], 0)
    seq = list(prompt)
    for _ in range(6):
        seq.append(int(np.argmax(rows[-1])))
        rows.append(call(seq[-1:], len(seq) - 1))
    want, report = ref.forward(getter(model), np.asarray(seq), TINY)
    at = [7, 15, *range(18, 25)]
    assert error(np.stack(rows), want[at]) < TOL
    state = engine.decode_model._state
    layers = [n for n in sorted(state, key=lambda n: int(n[1:].split("_")[0]))
              if "state_h" in state[n]]
    assert len(layers) == 6 == len(report["states"])
    for name, h, tail in zip(layers, report["states"], report["tails"]):
        assert state[name]["state_h"].dtype == jnp.float32
        assert state[name]["state_h"].shape == (3, 16, 128)
        assert error(state[name]["state_h"][1], h) < 1e-5
        assert error(state[name]["state_conv"][1], tail) < 1e-5
        # the slots no call touched hold nothing
        assert not np.asarray(state[name]["state_h"][0]).any()


def test_serve_decodes_what_the_reference_decodes(model):
    engine = serve(model)
    st = engine.stats()
    assert st["state_slots"] == 3 and st["state_resets"] == 0
    # six state-space layers: 16 x 128 float32 and 3 x 128 a slot
    assert st["state_bytes"] == 3 * 6 * (16 * 128 * 4 + 3 * 128 * 4)
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
    a cache of 128 rows, 2 query heads on the one KV head), a chunk rides
    as single-query rows past the slots and the state-space layers run
    them in order from the chunk's slot's state (eight tokens a program of
    the interpreted kernel): the same streams as the rectangle's, and the
    reference's."""
    big = dict(TINY, hidden_size=256, num_attention_heads=2,
               num_hidden_layers=2, attn_layer_period=2, attn_layer_offset=0,
               mamba_expand=1, mamba_dt_rank=4, intermediate_size=64)
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
    assert is_greedy(ff, ps[1], got[1], 4, big, 128)


def test_a_steps_span_carries_the_state_it_moves(model):
    """`state_rows` (the engine's) and `ssm_state_bytes` (the op's
    `DecodeState.step_counts`: one layer's h read and written for the
    decoding rows) on a step's span."""
    from flexflow_tpu import telemetry

    engine = serve(model)
    seen = []
    real = telemetry.span

    def span(name, **args):
        seen.append((name, args))
        return real(name, **args)

    telemetry.span, was = span, telemetry.span
    try:
        engine.generate(prompts(2, [5, 9], seed=3), max_new_tokens=4)
    finally:
        telemetry.span = was
    steps = [a for n, a in seen if n == "serve.step" and a.get("state_rows")]
    assert steps, [n for n, _ in seen][:20]
    for a in steps:
        assert a["ssm_state_bytes"] == 2 * a["active"] * 16 * 128 * 4
        assert a["state_rows"] == a["active"]


@pytest.mark.parametrize("how", ["prefix_cache", "prefix_sharing",
                                 "speculate", "disaggregate", "extract_kv",
                                 "admit_prefilled"])
def test_what_recurrent_state_cannot_follow_is_refused(model, how):
    """A matched prefix, a rewound cursor and the KV handoff are sound for
    attention only: a graph with state-space layers is refused by name,
    and the user is told which layers."""
    said = "recurrent layers .selective state-space layers: l0_attn"
    if how in ("prefix_cache", "prefix_sharing"):
        with pytest.raises(ValueError, match=said):
            serve(model, **{how: True})
    elif how == "speculate":
        with pytest.raises(NotImplementedError, match=said):
            serve(model, speculate=True, draft_model=model)
    elif how == "disaggregate":
        with pytest.raises(NotImplementedError, match=said):
            serve(model, disaggregate=True)
    else:
        engine = serve(model)
        with pytest.raises(NotImplementedError, match=said):
            if how == "extract_kv":
                engine.extract_kv(0, 4)
            else:
                engine.admit_prefilled(None, 0, None, None)
