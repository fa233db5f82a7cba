"""OLMoE through FFModel against the plain reference
(flexflow_tpu/models/olmoe_reference.py), at a small size on the CPU:
hidden 64, 4 heads of 16, 8 experts of width 32 with 2 a token, 2 layers,
sequences of 32. The program runs in float32 here, so what separates it
from the reference is the order of float32 sums (XLA's default matmul
precision on the CPU is float32 already, the reference's `highest` only
says so).
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import (
    AdamOptimizer, FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
)
from flexflow_tpu.models import (
    TransformerLMConfig, build_transformer_lm,
    olmoe_lm_config, olmoe_reference as ref,
)
from flexflow_tpu.ops import attention as attn_ops
from flexflow_tpu.ops import core as core_ops
from flexflow_tpu.ops import moe as moe_ops
from flexflow_tpu.ops.base import OpContext

SIZES = dict(vocab_size=96, hidden_size=64, num_heads=4, num_layers=2,
             sequence_length=32, attention_impl="xla", num_experts=8,
             num_experts_per_tok=2, moe_intermediate_size=32,
             router_aux_loss_coef=0.01)
MODEL = dict(num_layers=2, num_heads=4, num_experts_per_tok=2)
BATCH = 2
# float32 against float32: sums of 64-2048 terms in another order differ
# in the last few bits, 1e-6 relative; 2e-5 of the largest value leaves
# room for the softmaxes in between and is 200 times under bf16's step
TOL = 2e-5


def build(cfg, batch=BATCH, optimizer=None, seed=0):
    argv = sys.argv
    sys.argv = ["t", "-b", str(batch), "--mesh", "1,1,1,1", "--seed",
                str(seed)]
    try:
        ff = FFModel(FFConfig())
    finally:
        sys.argv = argv
    build_transformer_lm(ff, cfg, batch_size=batch)
    ff.compile(optimizer=optimizer or SGDOptimizer(lr=0.0),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY])
    return ff


def batch_of(seed=0, batch=BATCH, seq=32, vocab=96):
    toks = np.random.default_rng(seed).integers(
        0, vocab, (batch, seq + 1)).astype(np.int32)
    x = {"tokens": toks[:, :-1],
         "positions": np.tile(np.arange(seq, dtype=np.int32), (batch, 1))}
    return x, toks[:, 1:, None]


@pytest.fixture(scope="module")
def olmoe():
    ff = build(olmoe_lm_config(**SIZES))
    # unlike scales, so a norm that forgot its scale cannot pass
    rng = np.random.default_rng(1)
    for node, ws in ff._params.items():
        for name in ws:
            if name in ("scale", "q_norm", "k_norm"):
                ws[name] = jnp.asarray(
                    rng.uniform(0.5, 1.5, ws[name].shape), jnp.float32)
    return ff


def close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape
    scale = max(float(np.max(np.abs(b))), 1e-30)
    assert float(np.max(np.abs(a - b))) <= tol * scale, (
        float(np.max(np.abs(a - b))) / scale)


def test_logits_match_the_reference(olmoe):
    x, y = batch_of()
    olmoe.start_batch(x, y)
    logits = olmoe.forward()
    want, routing = ref.forward(olmoe._params, x["tokens"], x["positions"],
                                **MODEL)
    close(logits, want)
    for i, r in enumerate(routing):
        got = np.sort(np.asarray(olmoe._state[f"l{i}_moe"]["expert_ids"]), 1)
        assert np.array_equal(got, np.sort(np.asarray(r["ids"]), 1))


def test_loss_with_the_load_balancing_term_matches(olmoe):
    x, y = batch_of()
    olmoe.start_batch(x, y)
    got = float(olmoe.backward())
    want = float(ref.loss(olmoe._params, x["tokens"], x["positions"],
                          y[..., 0], router_aux_loss_coef=0.01, **MODEL))
    assert abs(got - want) <= TOL * abs(want)
    plain = float(ref.loss(olmoe._params, x["tokens"], x["positions"],
                           y[..., 0], router_aux_loss_coef=0.0, **MODEL))
    assert want - plain > 1e-3   # the term is there: about 0.01 x 2


def _grads(olmoe):
    x, y = batch_of()
    olmoe.start_batch(x, y)
    olmoe.backward()
    want = ref.grad(olmoe._params, x["tokens"], x["positions"], y[..., 0],
                    router_aux_loss_coef=0.01, **MODEL)
    return olmoe._grads, want


WEIGHTS = [(node, w) for node, ws in (
    ("wte", ["kernel"]),
    *[(f"l{i}_ln1", ["scale"]) for i in range(2)],
    *[(f"l{i}_attn", ["wq", "wk", "wv", "wo", "q_norm", "k_norm"])
      for i in range(2)],
    *[(f"l{i}_ln2", ["scale"]) for i in range(2)],
    *[(f"l{i}_moe", ["router", "gate", "up", "down"]) for i in range(2)],
    ("ln_f", ["scale"]), ("lm_head", ["kernel"])) for w in ws]


@pytest.fixture(scope="module")
def grads(olmoe):
    return _grads(olmoe)


@pytest.mark.parametrize("node,weight", WEIGHTS)
def test_gradient_of_every_weight_matches(olmoe, grads, node, weight):
    # gradients pass through every sum of the forward twice; 1e-4 of the
    # largest entry is still 40 times under bf16's step
    got, want = grads
    assert set(got[node]) == set(olmoe._params[node])
    close(got[node][weight], want[node][weight], tol=1e-4)


def test_every_weight_is_in_the_list(olmoe):
    assert sorted(WEIGHTS) == sorted(
        (node, w) for node, ws in olmoe._params.items() for w in ws)


# ------------------------------------------------ the ops alone

def test_rms_norm_op_alone():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(3, 5, 64)), jnp.float32)
    scale = jnp.asarray(rng.uniform(0.5, 1.5, 64), jnp.float32)
    close(core_ops.rms_norm(x, scale, 1e-5), ref.rms_norm(x, scale, 1e-5))


def test_rope_alone():
    rng = np.random.default_rng(3)
    b, s, h, hd = 2, 7, 4, 16
    x = jnp.asarray(rng.normal(size=(b, s, h * hd)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, 4096, (b, s)), jnp.int32)
    cos, sin = attn_ops.rope_cos_sin(pos, hd, 10000.0)
    got = attn_ops.apply_rope(x, cos, sin, h)
    rc, rs = ref.rope_cos_sin(pos, hd, 10000.0)
    xh = x.reshape(b, s, h, hd)
    want = xh * rc[:, :, None] + ref.rotate_half(xh) * rs[:, :, None]
    # angles up to 4096 radians in float32: cos and sin of them are good
    # to 4096 x 2^-24 = 2.4e-4
    close(got, want.reshape(b, s, h * hd), tol=1e-3)


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_attention_with_qk_norm_and_rope_alone(impl):
    rng = np.random.default_rng(4)
    b, s, d, h = 2, 32, 64, 4
    x = jnp.asarray(rng.normal(size=(b, s, d)), jnp.float32)
    pos = jnp.tile(jnp.arange(s, dtype=jnp.int32), (b, 1))
    w = {k: jnp.asarray(rng.normal(size=(d, d)) * 0.1, jnp.float32)
         for k in ("wq", "wk", "wv", "wo")}
    w["q_norm"] = jnp.asarray(rng.uniform(0.5, 1.5, d), jnp.float32)
    w["k_norm"] = jnp.asarray(rng.uniform(0.5, 1.5, d), jnp.float32)
    p = attn_ops.MultiHeadAttentionParams(
        attn_ops.AttentionFrontEnd(d, h, use_bias=False, rope_theta=10000.0,
                                   qk_norm=True),
        causal=True, impl=impl)
    (got,), _ = attn_ops._mha_forward(p, [x, x, x, pos], w, None,
                                      OpContext())
    with jax.default_matmul_precision("highest"):
        want = ref.attention(x, w, pos, num_heads=h, eps=1e-5, theta=10000.0)
    close(got, want, tol=1e-4)


def _moe_weights(rng, d=64, n=8, f=32):
    return {"router": jnp.asarray(rng.normal(size=(d, n)), jnp.float32),
            "gate": jnp.asarray(rng.normal(size=(n, d, f)) * 0.2, jnp.float32),
            "up": jnp.asarray(rng.normal(size=(n, d, f)) * 0.2, jnp.float32),
            "down": jnp.asarray(rng.normal(size=(n, f, d)) * 0.2,
                                jnp.float32)}


def _moe_reference(x, w, k):
    x2 = x.reshape(-1, x.shape[-1])
    with jax.default_matmul_precision("highest"):
        gates, ids, probs, _, _ = ref.route(x2, w["router"], k)
        return ref.experts(x2, gates, ids, w).reshape(x.shape), ids, probs


def test_expert_op_alone():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 32, 64)), jnp.float32)
    w = _moe_weights(rng)
    p = moe_ops.MoEMLPParams(8, 2, 32, aux_loss_coef=0.5)
    (got,), state = moe_ops._moe_mlp_forward(p, [x], w, None, OpContext())
    want, ids, probs = _moe_reference(x, w, 2)
    close(got, want)
    assert float(state["dropped_tokens"]) == 0.0
    with jax.default_matmul_precision("highest"):
        aux = ref.load_balancing_loss(probs, ids, 8)
    assert abs(float(state["aux_loss"]) - 0.5 * float(aux)) < 1e-5


def test_no_token_is_dropped_when_every_token_takes_the_same_experts():
    """A router that sends every token to experts 5 and 2: a capacity
    factor of 1 would keep a quarter of the assignments; here all 128 are
    computed, and the output is the reference's."""
    rng = np.random.default_rng(6)
    x = jnp.asarray(np.abs(rng.normal(size=(2, 32, 64))) + 0.1, jnp.float32)
    w = _moe_weights(rng)
    router = np.zeros((64, 8), np.float32)
    router[:, 5], router[:, 2] = 1.0, 0.5   # x > 0, so 5 then 2 always
    w["router"] = jnp.asarray(router)
    p = moe_ops.MoEMLPParams(8, 2, 32)
    (got,), state = moe_ops._moe_mlp_forward(p, [x], w, None, OpContext())
    ids = np.asarray(state["expert_ids"])
    assert np.all(ids[:, 0] == 5) and np.all(ids[:, 1] == 2)
    assert float(state["dropped_tokens"]) == 0.0
    assert float(state["load_max_over_mean"]) == 4.0   # 64 of a mean of 16
    close(got, _moe_reference(x, w, 2)[0])
    assert float(jnp.min(jnp.abs(got))) > 0.0   # every token got its sum


def test_forced_choice_at_near_ties_only():
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 8)) * 0.05, jnp.float32)
    _, own, probs, none, _ = ref.route(x, router, 2)
    assert not np.asarray(none).any()
    other = jnp.flip(jnp.argsort(probs, axis=-1), -1)[:, jnp.array([0, 2])]
    _, ids, _, tie, kept = ref.route(x, router, 2, program_ids=other,
                                     tie_margin=0.02)
    tie = np.asarray(tie)
    assert 0 < tie.sum() < 64
    assert np.array_equal(np.asarray(kept), np.asarray(own))
    assert np.array_equal(np.asarray(ids)[tie], np.asarray(other)[tie])
    assert np.array_equal(np.asarray(ids)[~tie], np.asarray(own)[~tie])


@pytest.mark.parametrize("k,n", [(256, 128), (128, 256)])
def test_pallas_grouped_matmul_against_ragged_dot(k, n):
    """The kernel the chip runs (interpreted here) against
    jax.lax.ragged_dot, forward, dX and dW, with an empty group, a group
    that ends inside a tile and rows past the last group. bf16 operands,
    float32 accumulation in both: they differ by one rounding of the
    result."""
    from flexflow_tpu.kernels import grouped_matmul as gm

    rng = np.random.default_rng(9)
    m, g = 512, 6
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(g, k, n)) * 0.1, jnp.bfloat16)
    dy = jnp.asarray(rng.normal(size=(m, n)), jnp.bfloat16)
    sizes = jnp.asarray([100, 0, 156, 37, 128, 61], jnp.int32)  # 482 of 512
    assert gm.pallas_tiling(x, w) == ((512, k, n), None)
    tiling = (128, 128, 128)    # four row tiles, groups end inside them

    def run(fn):
        out, vjp = jax.vjp(lambda x, w: fn(x, w, sizes), x, w)
        return (out, *vjp(dy))

    got = run(lambda x, w, s: gm.grouped_matmul_pallas(x, w, s, tiling,
                                                       interpret=True))
    want = run(gm.grouped_matmul_reference)
    for a, b in zip(got, want):
        close(a[:482] if a.shape[0] == m else a,
              b[:482] if b.shape[0] == m else b, tol=2**-7)
    assert gm.pallas_tiling(x.astype(jnp.float32), w)[1].endswith(
        "not bfloat16")
    assert "do not divide" in gm.pallas_tiling(x[:, :72], w)[1]
    # rows are padded to the row tile: a serving step's few assignments
    assert gm.pallas_tiling(x[:500], w) == ((512, k, n), None)
    assert [gm.padded_rows(m) for m in (8, 128, 130, 640, 2176, 131072)] == [
        128, 128, 256, 1024, 2560, 131072]


def test_grouped_matmul_takes_tiles_that_divide():
    """Experts 1,280 wide (Solar-Open2): 1,024 does not divide it, 640
    does; a decode step's few rows a group take a row tile of 128. The
    shapes the other cells run keep the tiling they were measured at. The
    kernel under such a tiling (interpreted) against ragged_dot."""
    from flexflow_tpu.kernels import grouped_matmul as gm

    def tiling(m, g, k, n):
        s = jax.ShapeDtypeStruct
        return gm.pallas_tiling(s((m, k), jnp.bfloat16),
                                s((g, k, n), jnp.bfloat16))[0]

    # solar2-serve-reason: 128 slots' 1,024 assignments, and with a chunk
    assert tiling(1024, 40, 4096, 1280) == (128, 1024, 640)
    assert tiling(3072, 40, 1280, 4096) == (128, 640, 1024)
    # many rows a group keep the large row tile under the smaller tiles
    assert tiling(131072, 40, 4096, 1280) == (512, 1024, 640)
    # dsv32-serve-sessions (decode, chunks of 64 / 128 / 256), olmoe-train-4k
    assert [tiling(m, 16, 7168, 2048) for m in (128, 640, 1152, 2176)] == [
        (128, 1024, 1024), *[(512, 1024, 1024)] * 3]
    assert tiling(131072, 64, 2048, 1024) == (512, 1024, 1024)
    assert gm._tile(1024, 1280) == 640 and gm._tile(1024, 72) == 0

    rng = np.random.default_rng(4)
    m, g, k, n = 384, 6, 128, 1280
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(g, k, n)) * 0.1, jnp.bfloat16)
    sizes = jnp.asarray([40, 0, 101, 7, 128, 61], jnp.int32)   # 337 of 384
    tiles = gm.pallas_tiling(x, w)[0]
    assert tiles == (128, 128, 640)
    got = gm.grouped_matmul_pallas(x, w, sizes, tiles, interpret=True)
    close(got[:337], gm.grouped_matmul_reference(x, w, sizes)[:337],
          tol=2**-7)


# ------------------------------------------------ the builder

GPT2_NAMES = ["tokens", "wte", "positions", "wpe", "embed_add",
              *[f"l{i}_{n}" for i in range(2)
                for n in ("ln1", "attn", "res1", "ln2", "ffn1", "gelu",
                          "ffn2", "res2")],
              "ln_f", "lm_head"]


def test_gpt2_values_give_the_graph_and_weight_names_as_before():
    cfg = TransformerLMConfig(vocab_size=96, hidden_size=64, num_heads=4,
                              num_layers=2, sequence_length=32,
                              attention_impl="xla")
    ff = build(cfg)
    assert [l.name for l in ff.layers] == [
        n for n in GPT2_NAMES if n not in ("tokens", "positions")]
    assert [type(l.params).__name__ for l in ff.layers[:7]] == [
        "EmbeddingParams", "EmbeddingParams", "ElementBinaryParams",
        "LayerNormParams", "MultiHeadAttentionParams", "ElementBinaryParams",
        "LayerNormParams"]
    weights = {node: sorted(ws) for node, ws in ff._params.items()}
    assert weights["l0_attn"] == ["bk", "bo", "bq", "bv", "wk", "wo", "wq",
                                  "wv"]
    assert weights["l1_ffn1"] == ["bias", "kernel"]
    assert weights["ln_f"] == ["bias", "scale"]
    assert sorted(weights) == sorted(
        n for n in GPT2_NAMES
        if n not in ("tokens", "positions", "embed_add")
        and not n.endswith(("res1", "res2", "gelu")))
    attn = ff.layers[4]
    assert len(attn.inputs) == 3 and not attn.params.front.rope_theta


@pytest.mark.parametrize("field", ["norm", "position", "mlp"])
def test_block_fields_are_checked(field):
    with pytest.raises(ValueError, match=field):
        TransformerLMConfig(**{field: "nope"})


def test_olmoe_serves_since_positions_reach_the_decode_ops(olmoe):
    """Until PR 31 the decode replay refused a RoPE or QK-norm layer."""
    prompts = [[5, 9, 2, 7], [3, 3, 8]]
    out = olmoe.serve(slots=2, max_new_tokens=2).generate(prompts)
    assert [len(o) for o in out] == [2, 2]


def test_olmoe_fits_and_the_loss_falls():
    ff = build(olmoe_lm_config(**SIZES), optimizer=AdamOptimizer(alpha=3e-3))
    x, y = batch_of(seed=8, batch=8)
    losses = []
    for _ in range(3):
        ff.reset_metrics()
        ff.fit(x, y, epochs=1, batch_size=BATCH, shuffle=False, verbose=False)
        losses.append(float(ff.get_perf_metrics().get_mean_loss()))
    assert losses[-1] < losses[0] - 0.05, losses
    for i in range(2):
        assert float(ff._state[f"l{i}_moe"]["dropped_tokens"]) == 0.0
