"""MiMo-V2-Flash through the normal path at a small size (PR 41): window
and global attention layers mixed, a sink a head in the window layers,
key heads of 24 and value heads of 16, RoPE on a head's first 8 lanes at
two thetas, two KV-head counts, the values' scale, a leading dense layer
then sigmoid-routed experts; the training-shaped op and the whole LM
against the float32 reference (models/mimo_v2_flash_reference.py) on
seeded weights. The serving path is tests/test_mimo_v2_flash_serving.py's.
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
from flexflow_tpu.kernels.dispatch import KernelFallbackWarning
from flexflow_tpu.models import (
    build_transformer_lm, mimo_v2_flash_lm_config,
    mimo_v2_flash_reference as ref,
)
from flexflow_tpu.ops.base import OpContext, get_op_def

# hidden 64; 4 query heads of 24 / 16 over 1 (global) and 2 (window) KV
# heads; a window of 6; layers [global + dense, window, window, global];
# 16 experts of 24, 4 a token, all held
TINY = dict(
    model_type="mimo_v2_flash", hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=1, head_dim=24, v_head_dim=16,
    swa_num_attention_heads=4, swa_num_key_value_heads=2, swa_head_dim=24,
    swa_v_head_dim=16, vocab_size=97, intermediate_size=96,
    moe_intermediate_size=24, layernorm_epsilon=1e-5, rope_theta=5000000,
    swa_rope_theta=10000, partial_rotary_factor=0.334, sliding_window=6,
    hybrid_layer_pattern=[0, 1, 1, 0], moe_layer_freq=[0, 1, 1, 1],
    add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False,
    attention_value_scale=0.707, attention_bias=False, hidden_act="silu",
    n_routed_experts=16, n_shared_experts=None, num_experts_per_tok=4,
    norm_topk_prob=True, scoring_func="sigmoid", n_group=1, topk_group=1,
    topk_method="noaux_tc", routed_scaling_factor=None)
SEQ = 40
# float32 against float32, as a share of the largest logit: the sums run
# in another order, nothing else differs
TOL = 2e-5


def build(config=TINY, seq=SEQ, batch=2, impl="xla"):
    argv = sys.argv
    sys.argv = ["t", "-b", str(batch), "--mesh", "1,1,1,1",
                "--no-verify-plan"]
    try:
        cfg = FFConfig()
    finally:
        sys.argv = argv
    ff = FFModel(cfg)
    build_transformer_lm(ff, mimo_v2_flash_lm_config(
        config, sequence_length=seq, attention_impl=impl,
        initializer_range=0.1, embedding_range=1.0, sink_range=2.0),
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


def test_the_config_builder_reads_the_published_keys():
    c = mimo_v2_flash_lm_config(TINY, sequence_length=8)
    assert c.layer_pattern == ("mha", "swa", "swa", "mha")
    assert (c.num_kv_heads, c.rope_theta) == (1, 5e6)
    assert c.swa == dict(num_kv_heads=2, rope_theta=1e4, window=6, sink=True)
    assert (c.head_dim, c.v_head_dim, c.rope_dim) == (24, 16, 8)
    assert (c.value_scale, c.first_k_dense) == (0.707, 1)
    assert c.moe_routing["scoring"] == "sigmoid"
    assert c.moe_routing["routed_scaling_factor"] == 1.0
    cut = mimo_v2_flash_lm_config(
        {**TINY, "n_routed_experts": 4, "experts_held": [4, 4],
         "experts_routed": 16}, sequence_length=8)
    assert cut.num_experts == 16 and cut.moe_routing["experts_held"] == (4, 4)
    with pytest.raises(NotImplementedError, match="published block"):
        mimo_v2_flash_lm_config({**TINY, "swa_head_dim": 32},
                                sequence_length=8)


def test_what_differs_by_kind_is_the_layers_front_end(model):
    fronts = {l.name: l.params.front for l in model.layers
              if l.op_type == OT.OP_MULTIHEAD_ATTENTION}
    full, swa = fronts["l0_attn"], fronts["l1_attn"]
    assert fronts["l3_attn"] == full and fronts["l2_attn"] == swa
    assert (full.window, full.sink, full.kv_heads, full.rope_theta) == (
        0, False, 1, 5e6)
    assert (swa.window, swa.sink, swa.kv_heads, swa.rope_theta) == (
        6, True, 2, 1e4)
    for f in (full, swa):
        assert (f.head_dim, f.v_head_dim, f.rope_dim, f.value_scale) == (
            24, 16, 8, 0.707)
        assert (f.q_width, f.o_width) == (96, 64)
    assert (full.kv_width, full.v_width) == (24, 16)
    assert (swa.kv_width, swa.v_width) == (48, 32)
    assert full.attend_scope == "gqa.attend"
    assert swa.attend_scope == "swa.attend"
    shapes = {k: v.shape for k, v in model._params["l1_attn"].items()}
    assert shapes == {"wq": (64, 96), "wk": (64, 48), "wv": (64, 32),
                      "wo": (64, 64), "sink": (4,)}
    assert "sink" not in model._params["l0_attn"]
    # the sinks are drawn, not zeros: a dropped sink has to show
    assert float(jnp.std(model._params["l1_attn"]["sink"])) > 0.5


def test_the_routers_correction_bias_is_zeros(model):
    """No published key gives the trained bias, and the layer's own draw,
    N(0, 0.02) beside sigmoid scores a few thousandths apart, would decide
    which experts are loaded (PERF.md section 6, PR 41): MiMo's builder
    asks for zeros, every other builder leaves the layer's draw."""
    assert mimo_v2_flash_lm_config(
        TINY, sequence_length=8).router_bias_range == 0.0
    moe = [name for name in model._params if name.endswith("_moe")]
    assert moe == ["l1_moe", "l2_moe", "l3_moe"]
    for name in moe:
        assert not np.asarray(model._params[name]["router_bias"]).any()
        assert float(jnp.std(model._params[name]["router"])) > 0
    from flexflow_tpu.models import TransformerLMConfig

    assert TransformerLMConfig().router_bias_range is None


@pytest.mark.parametrize("layer", [0, 1])
def test_the_training_shaped_op_is_the_references_attention(model, layer):
    """One layer's op alone: the band and the sink (layer 1), the whole
    past (layer 0), heads of 24 / 16, RoPE on 8 lanes at the kind's theta,
    the kind's KV heads, the values' scale."""
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
            xb = ref.dsa.rms_norm(x[b], ones, d.eps)
            (yb,), _ = get_op_def(OT.OP_MULTIHEAD_ATTENTION).forward(
                node.params, [xb[None], xb[None], xb[None], pos[:1]],
                weights, None, OpContext(training=False, mesh=None))
            want = ref.attention(x[b], weights, np.arange(SEQ), d,
                                 scale=ones, row_block=16)
            assert yb.shape == (1, SEQ, 64)
            assert error(yb[0], np.asarray(want)) < TOL


def test_the_whole_lm_is_the_reference(model, tokens):
    logits = forward(model, tokens)
    for b in range(2):
        want, notes = ref.forward(getter(model), tokens[b], TINY)
        assert error(logits[b], want) < TOL
        assert len(notes) == 4 and "gap" in notes[1] and not notes[0]


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
    assert k.shape == (SEQ, 24) and v.shape == (SEQ, 16)


def test_flash_takes_the_einsum_and_says_so(tokens, monkeypatch):
    """The packed kernels keep one head size and attend the whole past:
    under `flash` such a layer takes the einsum, with the repo's warning
    where that is said (on a TPU)."""
    ff = build(impl="flash", batch=1)
    want, _ = ref.forward(getter(ff), tokens[0], TINY)
    assert error(forward(ff, tokens[:1])[0], want) < TOL
    node = next(l for l in ff.layers if l.name == "l1_attn")
    assert node.params.impl == "flash"
    x = jnp.ones((1, 8, 64), jnp.float32)
    pos = jnp.arange(8, dtype=jnp.int32)[None]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.warns(KernelFallbackWarning, match="window 6, sink True"):
        get_op_def(OT.OP_MULTIHEAD_ATTENTION).forward(
            node.params, [x, x, x, pos], ff._params["l1_attn"], None,
            OpContext(training=False, mesh=None))


def test_the_sixteen_shares_add_up_to_the_uncut_layer(model):
    """The share is tied to the model: an expert layer cut into 4 shares
    of 4 experts (each told which it holds, routing over all 16) gives, all
    shares summed, what the uncut reference gives for the whole layer."""
    from flexflow_tpu.ops.moe import MoEMLPParams

    w = {k: jnp.asarray(v, jnp.float32)
         for k, v in model._params["l1_moe"].items()}
    x = jnp.asarray(np.random.default_rng(5).normal(size=(12, 64)),
                    jnp.float32)
    r = ref.routing(TINY)
    none = jnp.full((12, r.k), -1, jnp.int32)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.expert_layer(
            x, {n: w[n] for n in ref.EXPERT_WEIGHTS}, none, 0.0, r=r, first=0)
        shares = []
        for first in range(0, 16, 4):
            held = {n: w[n][first:first + 4] if n in ("gate", "up", "down")
                    else w[n] for n in ref.EXPERT_WEIGHTS}
            part, _ = ref.expert_layer(x, held, none, 0.0, r=r, first=first)
            shares.append(np.asarray(part))
            # and the program's own share of the same experts
            p = MoEMLPParams(16, 4, 24, scoring="sigmoid", n_group=1,
                             topk_group=1, norm_topk_prob=True,
                             routed_scaling_factor=1.0,
                             experts_held=(first, 4))
            state = {k: jnp.zeros((), jnp.int32)
                     for k in ("assignments_total", "dropped_total")}
            (mine,), _ = get_op_def(OT.OP_MOE_MLP).forward(
                p, [x[None]], {**held, **state}, None,
                OpContext(training=False, mesh=None))
            assert error(mine[0], np.asarray(part)) < TOL
    assert error(sum(shares), np.asarray(whole)) < TOL
    assert float(np.max(np.abs(shares[0]))) > 0


def test_what_cannot_carry_a_window_group_refuses_by_name(model):
    from flexflow_tpu.ops.base import REWIND
    from flexflow_tpu.serving.decode_graph import decode_states, refuse

    assert [name for name, state in decode_states(model).items()
            if state.window] == ["l1_attn", "l2_attn"]
    with pytest.raises(NotImplementedError, match="window attention layers "
                       r"\(l1_attn"):
        refuse(model, "a test", REWIND)
    with pytest.raises(NotImplementedError, match="speculative"):
        model.serve(slots=2, max_seq_len=SEQ, speculate=True,
                    draft_model=model)
    with pytest.raises(NotImplementedError, match="disagg"):
        model.serve(slots=2, max_seq_len=SEQ, disaggregate=True)
    eng = model.serve(slots=2, max_seq_len=SEQ, prefill_chunk=8,
                      kv_block_size=4)
    with pytest.raises(NotImplementedError, match="extract_kv"):
        eng.extract_kv(0, 4)
    from flexflow_tpu.serving.scheduler import Request

    with pytest.raises(NotImplementedError, match="admit_prefilled"):
        eng.admit_prefilled(Request(prompt=[1, 2], max_new_tokens=1,
                                    temperature=0.0, eos_id=None), 3,
                            None, None)
