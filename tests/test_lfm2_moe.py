"""LFM2-MoE through FFModel against the plain reference
(flexflow_tpu/models/lfm2_moe_reference.py) at a small size on the CPU
(PR 60): hidden 64, 4 query heads on 2 KV heads of 16, 8 experts of 48 with
2 a token, layers [conv, full_attention, conv, conv] with one leading dense
layer, a tied head over 97 tokens, sequences of 24. The gated short
convolution alone, its plans on four devices, the grouped flash path, the
four shares of an expert layer, and `fit()`.
"""

import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import (
    AdamOptimizer, FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
)
from flexflow_tpu.fftype import OperatorType as OT
from flexflow_tpu.models import (
    TransformerLMConfig, build_transformer_lm, lfm2_moe_lm_config,
    lfm2_moe_reference as ref,
)
from flexflow_tpu.ops import attention as attn_ops
from flexflow_tpu.ops import moe as moe_ops
from flexflow_tpu.ops import recurrent
from flexflow_tpu.ops.base import OpContext, get_op_def
from flexflow_tpu.ops.short_conv import ShortConvFrontEnd, ShortConvParams

TINY = dict(
    model_type="lfm2_moe", hidden_size=64, num_hidden_layers=4,
    layer_types=["conv", "full_attention", "conv", "conv"],
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
    moe_intermediate_size=48, num_dense_layers=1, num_experts=8,
    num_experts_per_tok=2, vocab_size=97, norm_eps=1e-5, rope_theta=1000000,
    conv_L_cache=3, conv_bias=False, norm_topk_prob=True,
    routed_scaling_factor=1, use_expert_bias=True,
    max_position_embeddings=128)
MODEL = dict(layer_types=tuple(TINY["layer_types"]), num_dense_layers=1,
             num_heads=4, num_kv_heads=2, num_experts_per_tok=2)
SEQ, BATCH = 24, 2
# float32 against float32: sums in another order differ in the last few
# bits; 2e-5 of the largest value is 200 times under bf16's step
TOL = 2e-5


def build(config=TINY, seq=SEQ, batch=BATCH, flags=(), optimizer=None,
          impl="xla"):
    argv = sys.argv
    sys.argv = ["t", "-b", str(batch), "--mesh", "1,1,1,1",
                "--no-verify-plan", *flags]
    try:
        ff = FFModel(FFConfig())
    finally:
        sys.argv = argv
    build_transformer_lm(ff, lfm2_moe_lm_config(
        config, sequence_length=seq, attention_impl=impl,
        initializer_range=0.1), batch_size=batch)
    ff.compile(optimizer=optimizer or SGDOptimizer(lr=0.0),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY])
    return ff


def batch_of(seed=0, batch=BATCH, seq=SEQ, vocab=97):
    toks = np.random.default_rng(seed).integers(
        0, vocab, (batch, seq + 1)).astype(np.int32)
    x = {"tokens": toks[:, :-1],
         "positions": np.tile(np.arange(seq, dtype=np.int32), (batch, 1))}
    return x, toks[:, 1:, None]


def unlike_scales(ff, seed=1):
    """Norm scales and the routers' bias off their initial ones and zeros,
    so that a norm that forgot its scale or a choice that forgot its bias
    cannot pass."""
    rng = np.random.default_rng(seed)
    for ws in ff._params.values():
        for name in ws:
            if name in ("scale", "q_norm", "k_norm"):
                ws[name] = jnp.asarray(
                    rng.uniform(0.5, 1.5, ws[name].shape), jnp.float32)
            if name == "router_bias":
                ws[name] = jnp.asarray(
                    rng.normal(0, 0.05, ws[name].shape), jnp.float32)


@pytest.fixture(scope="module")
def lfm2():
    ff = build()
    unlike_scales(ff)
    return ff


def ref_forward(params, x, **more):
    return jax.jit(lambda p, t, pos: ref.forward(p, t, pos, **MODEL, **more))(
        params, x["tokens"], x["positions"])


def ref_loss_and_grad(params, x, y, **more):
    return jax.jit(jax.value_and_grad(
        lambda p, t, pos, lab: ref.loss(p, t, pos, lab, **MODEL, **more)))(
        params, x["tokens"], x["positions"], y[..., 0])


def program_loss_and_grad(ff, x, y):
    """(loss, gradients, logits, the state handed back) of one jitted
    program, as a training step is: the experts it chose are in that
    state (two programs round apart and part at near-ties)."""
    ff.start_batch(x, y)
    xs, labels = ff._current_batch
    inner = ff.executor.make_loss_fn(ff._state, xs, labels, ff._rng)
    (loss, (logits, state, _)), grads = jax.jit(
        jax.value_and_grad(inner, has_aux=True))(ff._params)
    return loss, grads, logits, state


def close(a, b, tol=TOL):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape
    scale = max(float(np.max(np.abs(b))), 1e-30)
    assert float(np.max(np.abs(a - b))) <= tol * scale, (
        float(np.max(np.abs(a - b))) / scale)


# ------------------------------------------------ the model, float32

def test_logits_match_the_reference(lfm2):
    x, y = batch_of()
    lfm2.start_batch(x, y)
    logits = lfm2.forward()
    want, routing = ref_forward(lfm2._params, x)
    close(logits, want)
    assert len(routing) == 3
    for i, r in zip((1, 2, 3), routing):
        got = np.sort(np.asarray(lfm2._state[f"l{i}_moe"]["expert_ids"]), 1)
        assert np.array_equal(got, np.sort(np.asarray(r["ids"]), 1))
        # the bias moved a choice somewhere: it takes part
        plain = np.sort(np.asarray(
            jax.lax.top_k(r["scores"], 2)[1]), 1)
        assert not np.array_equal(got, plain)


def test_loss_matches(grads):
    (got, _), (want, _) = grads
    assert abs(float(got) - float(want)) <= TOL * abs(float(want))


WEIGHTS = [(node, w) for node, ws in (
    ("wte", ["kernel"]),
    *[(f"l{i}_ln1", ["scale"]) for i in range(4)],
    *[(f"l{i}_attn", ["w_in", "conv", "w_out"]) for i in (0, 2, 3)],
    ("l1_attn", ["wq", "wk", "wv", "wo", "q_norm", "k_norm"]),
    *[(f"l{i}_ln2", ["scale"]) for i in range(4)],
    *[(f"l0_ffn_{t}", ["kernel"]) for t in ("gate", "up", "down")],
    *[(f"l{i}_moe", ["router", "router_bias", "gate", "up", "down"])
      for i in (1, 2, 3)],
    ("ln_f", ["scale"])) for w in ws]


@pytest.fixture(scope="module")
def grads(lfm2):
    x, y = batch_of()
    return program_loss_and_grad(lfm2, x, y)[:2], ref_loss_and_grad(
        lfm2._params, x, y)


@pytest.mark.parametrize("node,weight", WEIGHTS)
def test_gradient_of_every_weight_matches(lfm2, grads, node, weight):
    # gradients pass through every sum of the forward twice; 1e-4 of the
    # largest entry is still 40 times under bf16's step. The tied
    # embedding's is the sum of the gather's and the head's
    (_, got), (_, want) = grads
    assert set(got[node]) == set(lfm2._params[node])
    if weight == "router_bias":
        # the bias enters the choice only
        assert not np.any(np.asarray(got[node][weight]))
        assert not np.any(np.asarray(want[node][weight]))
        return
    close(got[node][weight], want[node][weight], tol=1e-4)


def test_every_weight_is_in_the_list(lfm2):
    assert sorted(WEIGHTS) == sorted(
        (node, w) for node, ws in lfm2._params.items() for w in ws)
    assert "lm_head" not in lfm2._params    # tied: one table


def test_bf16_logits_and_gradients_stay_near_the_reference():
    """Under --dtype bf16 (float32 masters) the logits stay within 0.03 of
    the largest, the loss within 0.5 % and every gradient within 0.15 of
    its largest entry: bf16 keeps 8 bits (2^-8 = 0.004 a rounding), a logit
    passes through some 30 rounded sums and a gradient through twice that
    (read here: logits 0.01, gradients up to 0.09 at matrices drawn from
    N(0, 0.1), five times the cell's spread); the near-ties the program
    routes otherwise are given to the reference. A float32 program reads
    1e-6 and 1e-5 (above), so either limit is a thousand times its
    reading and still under a lost term (a forgotten tap moves a logit by
    a tenth)."""
    ff = build(flags=("--dtype", "bf16"))
    x, y = batch_of()
    loss, got, logits, state = program_loss_and_grad(ff, x, y)
    ids = [np.asarray(state[f"l{i}_moe"]["expert_ids"]) for i in (1, 2, 3)]
    at_ties = dict(program_ids=ids, tie_margin=0.05)
    want, _ = ref_forward(ff._params, x, **at_ties)
    close(logits, want, tol=0.03)
    want_loss, want = ref_loss_and_grad(ff._params, x, y, **at_ties)
    assert abs(float(loss) - float(want_loss)) <= 0.005 * float(want_loss)
    for node, w in WEIGHTS:
        if w != "router_bias":
            close(got[node][w], want[node][w], tol=0.15)


# ------------------------------------------------ a held share

@pytest.mark.parametrize("eps", [0.0, 1e-6])
def test_the_four_shares_add_up_to_the_whole_layer(eps):
    """The guide's share test: the layer with `experts_held` (0, 8), (8,
    8), (16, 8), (24, 8) of 32 experts, each given its own experts' rows of
    the whole layer's matrices, add up to the uncut reference's output;
    the gradients with respect to the input likewise."""
    rng = np.random.default_rng(5)
    d, f, n, k, t = 32, 24, 32, 4, 40
    whole = {
        "router": rng.normal(0, 0.5, (d, n)), "router_bias":
        rng.normal(0, 0.05, (n,)), "gate": rng.normal(0, 0.2, (n, d, f)),
        "up": rng.normal(0, 0.2, (n, d, f)),
        "down": rng.normal(0, 0.2, (n, f, d))}
    whole = {k_: jnp.asarray(v, jnp.float32) for k_, v in whole.items()}
    x = jnp.asarray(rng.normal(0, 1, (t, d)), jnp.float32)
    op = get_op_def(OT.OP_MOE_MLP)

    def share(x, first):
        p = moe_ops.MoEMLPParams(
            n, k, f, scoring="sigmoid", norm_topk_prob=True,
            norm_topk_eps=eps, experts_held=(first, 8))
        w = dict(whole, **{m: whole[m][first:first + 8]
                           for m in ("gate", "up", "down")})
        (y,), _ = op.forward(p, [x], w, {}, OpContext(training=True))
        return y

    def reference(x):
        with jax.default_matmul_precision("highest"):
            gates, ids, *_ = ref.route(
                x, whole["router"], whole["router_bias"], k,
                norm_topk_prob=True, routed_scaling_factor=1.0)
            if not eps:   # the reference's 1e-6 taken out again
                s = jnp.take_along_axis(
                    jax.nn.sigmoid(x @ whole["router"]), ids, axis=-1)
                gates = s / jnp.sum(s, axis=-1, keepdims=True)
            return ref.experts(x, gates, ids, whole, (0, n))

    def summed(x):
        return sum(share(x, first) for first in (0, 8, 16, 24))

    weight = jnp.asarray(rng.normal(0, 1, (t, d)), jnp.float32)

    def with_grad(fn):
        return jax.jit(jax.value_and_grad(
            lambda x: (lambda y: (jnp.sum(y * weight), y))(fn(x)),
            has_aux=True))(x)

    (_, got), got_dx = with_grad(summed)
    (_, want), want_dx = with_grad(reference)
    close(got, want)
    close(got_dx, want_dx, tol=1e-4)
    # a share alone is a part: it differs from the whole
    assert float(jnp.max(jnp.abs(jax.jit(share, static_argnums=1)(x, 0)
                                 - want))) > 1e-3


def test_rows_no_expert_computed_give_no_gradient(monkeypatch):
    """On the chip the grouped matmul leaves the rows past its groups' sum
    unwritten, in the forward and in dX alike (first met as a NaN loss in
    the cell's second step, PR 60). A held share sorts the assignments to
    experts held elsewhere there: with those rows poisoned, the layer's
    output and every gradient are what they are without."""
    import flexflow_tpu.kernels.grouped_matmul as gm

    real = gm.grouped_matmul

    @jax.custom_vjp
    def unwritten(lhs, rhs, sizes):
        live = jnp.arange(lhs.shape[0]) < jnp.sum(sizes)
        return jnp.where(live[:, None], real(lhs, rhs, sizes), jnp.nan)

    def fwd(lhs, rhs, sizes):
        return unwritten(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        live = (jnp.arange(lhs.shape[0]) < jnp.sum(sizes))[:, None]
        _, vjp = jax.vjp(lambda a, b: real(a, b, sizes),
                         jnp.where(live, lhs, 0), rhs)
        dl, dr = vjp(jnp.where(live, g, 0))
        return jnp.where(live, dl, jnp.nan), dr, None

    unwritten.defvjp(fwd, bwd)
    rng = np.random.default_rng(6)
    d, f, n, k, t = 16, 8, 8, 2, 24
    w = {"router": rng.normal(0, 0.5, (d, n)), "router_bias": np.zeros(n),
         "gate": rng.normal(0, 0.3, (4, d, f)),
         "up": rng.normal(0, 0.3, (4, d, f)),
         "down": rng.normal(0, 0.3, (4, f, d))}
    w = {k_: jnp.asarray(v, jnp.float32) for k_, v in w.items()}
    x = jnp.asarray(rng.normal(0, 1, (t, d)), jnp.float32)
    p = moe_ops.MoEMLPParams(n, k, f, scoring="sigmoid", norm_topk_prob=True,
                             experts_held=(2, 4))

    def run(x, w):
        (y,), state = get_op_def(OT.OP_MOE_MLP).forward(
            p, [x], w, {}, OpContext(training=True))
        return jnp.sum(y * y), state["dropped_tokens"]

    clean = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(x, w)
    monkeypatch.setattr(gm, "grouped_matmul",
                        lambda a, b, sizes, mesh=None: unwritten(a, b, sizes))
    dirty = jax.value_and_grad(run, argnums=(0, 1), has_aux=True)(x, w)
    assert float(clean[0][1]) == 0.0    # and some rows ARE past the sum
    (y0, _), (dx0, dw0) = clean
    (y1, _), (dx1, dw1) = dirty
    assert np.isfinite(float(y1)) and float(y1) == float(y0)
    close(dx1, dx0)
    for name in ("router", "gate", "up", "down"):
        close(dw1[name], dw0[name])


def test_the_normalisers_epsilon_is_in_the_gates():
    p = moe_ops.MoEMLPParams(8, 2, 4, scoring="sigmoid", norm_topk_prob=True,
                             norm_topk_eps=0.5)
    x = jnp.ones((3, 4))
    router = jnp.asarray(np.random.default_rng(0).normal(0, 1, (4, 8)),
                         jnp.float32)
    gates, ids, scores = moe_ops.moe_route_sigmoid(x, router, None, p)
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    close(gates, chosen / (jnp.sum(chosen, -1, keepdims=True) + 0.5))
    assert moe_ops.MoEMLPParams(8, 2, 4).norm_topk_eps == 0.0


# ------------------------------------------------ the short convolution

def _sconv(d=32, taps=3, seed=2):
    rng = np.random.default_rng(seed)
    front = ShortConvFrontEnd(embed_dim=d, conv_kernel=taps)
    w = {"w_in": rng.normal(0, 0.3, (d, 3, d)),
         "conv": rng.uniform(-0.6, 0.6, (taps, d)),
         "w_out": rng.normal(0, 0.3, (d, d))}
    return front, {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}


@pytest.mark.parametrize("taps", [3, 4])
def test_short_conv_against_lax_conv_and_a_shifted_sum(taps):
    front, w = _sconv(taps=taps)
    x = jnp.asarray(np.random.default_rng(3).normal(0, 1, (2, 11, 32)),
                    jnp.float32)
    (y,), _ = get_op_def(OT.OP_SHORT_CONV).forward(
        ShortConvParams(front), [x], w, {}, OpContext(training=True))
    bcx = jnp.einsum("bsd,dge->bsge", x, w["w_in"])
    B, C, xs = bcx[:, :, 0], bcx[:, :, 1], bcx[:, :, 2]
    u = B * xs
    # torch's Conv1d(groups=channels, padding=taps - 1) cut to the sequence
    c = jax.lax.conv_general_dilated(
        u.transpose(0, 2, 1), w["conv"].T[:, None, :], (1,),
        [(taps - 1, 0)], feature_group_count=32,
        dimension_numbers=("NCH", "OIH", "NCH")).transpose(0, 2, 1)
    close(y, (C * c) @ w["w_out"])
    # the shifted sum written out: c[t] = sum_j w[j] u[t - (taps - 1) + j]
    shifted = jnp.zeros_like(u)
    for j in range(taps):
        back = taps - 1 - j
        shifted = shifted + w["conv"][j] * jnp.pad(
            u, ((0, 0), (back, 0), (0, 0)))[:, :u.shape[1]]
    close(y, (C * shifted) @ w["w_out"])
    close(y, ref.short_conv(x, w))
    # causal: a later token moves no earlier output
    x2 = x.at[:, 7].add(1.0)
    (y2,), _ = get_op_def(OT.OP_SHORT_CONV).forward(
        ShortConvParams(front), [x2], w, {}, OpContext(training=True))
    assert np.array_equal(np.asarray(y[:, :7]), np.asarray(y2[:, :7]))
    assert not np.allclose(np.asarray(y[:, 7]), np.asarray(y2[:, 7]))


def test_causal_conv_default_is_the_program_it_was():
    """`activation` defaults to what `causal_conv` did before it had the
    argument: the delta rule's and the state-space layer's calls (taps
    without and with a bias) lower to the text of the old body."""
    def old(taps, window, tokens, bias=None):
        taps = taps.astype(jnp.float32)
        wf = window.astype(jnp.float32)
        y = sum(taps[i] * wf[:, i:i + tokens] for i in range(taps.shape[0]))
        if bias is not None:
            y = y + bias.astype(jnp.float32)
        return y * jax.nn.sigmoid(y)

    def text(fn, with_bias):
        def run(taps, window, bias):
            return fn(taps, window, 8, bias if with_bias else None)

        return jax.jit(run).lower(
            jax.ShapeDtypeStruct((4, 16), jnp.float32),
            jax.ShapeDtypeStruct((2, 11, 16), jnp.bfloat16),
            jax.ShapeDtypeStruct((16,), jnp.float32)).as_text()

    for with_bias in (False, True):    # delta's call, mamba's call
        assert text(recurrent.causal_conv, with_bias) == text(old, with_bias)
    window = jnp.ones((1, 5, 4))
    taps = jnp.full((3, 4), 0.5)
    plain = recurrent.causal_conv(taps, window, 3, activation=None)
    assert np.allclose(np.asarray(plain), 1.5)
    with pytest.raises(ValueError, match="activation"):
        recurrent.causal_conv(taps, window, 3, activation="relu")


def _sconv_model(mesh_axes, plan):
    sys.argv = ["t", "--seed", "0"]
    config = FFConfig()
    config.mesh_axis_sizes = mesh_axes
    config.batch_size = 4
    ff = FFModel(config)
    x = ff.create_tensor((4, 12, 32), name="x")
    front = ShortConvFrontEnd(embed_dim=32)
    t = ff.short_conv(x, front, name="mix")
    ff.dense(t, 1, use_bias=False, name="head")
    if plan == "channel":
        from flexflow_tpu.parallel.strategies import Strategy

        s = Strategy()
        for name, spec in front.channel_parallel("model"):
            s.set_weight("mix", name, spec)
        s.set_output("mix", 0, (("data",), (), ()))
        ff.set_strategy(s)
    ff.compile(optimizer=SGDOptimizer(lr=0.05),
               loss_type=LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE)
    return ff


@pytest.mark.parametrize("mesh,plan", [((4, 1, 1, 1), "dp"),
                                       ((1, 4, 1, 1), "channel")])
def test_short_conv_plans_match_one_device(mesh, plan):
    """Data parallel and the channel split (`w_in` by column, the taps by
    channel, `w_out` by row) on four devices train as one device does."""
    rs = np.random.RandomState(0)
    x = rs.randn(8, 12, 32).astype(np.float32)
    y = rs.randn(8, 12, 1).astype(np.float32)
    one, four = _sconv_model((1, 1, 1, 1), "dp"), _sconv_model(mesh, plan)
    assert four.mesh.devices.size == 4
    if plan == "channel":
        spec = four._params["mix"]["w_in"].sharding.spec
        assert tuple(spec) == (None, None, "model"), spec
    for ff in (one, four):
        ff.fit(x, y, epochs=2, batch_size=4, shuffle=False, verbose=False)
    for name in ("w_in", "conv", "w_out"):
        np.testing.assert_allclose(
            np.asarray(four._params["mix"][name]),
            np.asarray(one._params["mix"][name]), rtol=2e-5, atol=2e-6)


def test_search_offers_and_prices_the_channel_split():
    """compile() with the search on (no --mesh) over the virtual devices
    returns a plan for the LFM2 graph; the short convolution has a `dp`
    and a `tp_sconv` candidate, both priced, and nothing falls back to a
    reference kernel with a warning."""
    from flexflow_tpu.kernels.dispatch import KernelFallbackWarning
    from flexflow_tpu.search import (
        CostModel, UnitySearch, machine_model_for_mesh,
    )

    argv = sys.argv
    sys.argv = ["t", "-b", "4", "--budget", "4",
                "--enable-parameter-parallel", "--enable-attribute-parallel"]
    try:
        config = FFConfig()
    finally:
        sys.argv = argv
    config.mesh_axis_sizes = (2, 2, 1, 1)
    ff = FFModel(config)
    build_transformer_lm(ff, lfm2_moe_lm_config(
        TINY, sequence_length=SEQ, initializer_range=0.1), batch_size=4)
    with warnings.catch_warnings():
        warnings.simplefilter("error", KernelFallbackWarning)
        ff.compile(
            optimizer=AdamOptimizer(),
            loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
            metrics=[MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY])
    s = UnitySearch(ff.graph, ff.mesh, config,
                    CostModel(machine_model_for_mesh(ff.mesh)))
    conv = [n for n in s.order if n.op_type == OT.OP_SHORT_CONV]
    assert len(conv) == 3
    by_name = {c.name: c for c in s.node_configs(conv[0])}
    assert set(by_name) == {"dp", "tp_sconv"}
    assert dict(by_name["tp_sconv"].weight_specs)["w_in"] == \
        jax.sharding.PartitionSpec(None, None, "model")
    for name in by_name:
        choice = {n.guid: (by_name[name] if n is conv[0]
                           else s.node_configs(n)[0])
                  for n in s.order if s.node_configs(n)}
        seconds, memory = s.evaluate(choice)
        assert seconds > 0 and memory > 0
    flops = get_op_def(OT.OP_SHORT_CONV).flops(
        conv[0].params, [(4, SEQ, 64)], [(4, SEQ, 64)])
    assert flops == 2.0 * 4 * SEQ * (64 * 192 + 64 * 3 + 64 * 64)
    x, y = batch_of(batch=4)
    ff.fit(x, y, epochs=1, batch_size=4, shuffle=False, verbose=False)
    assert np.isfinite(float(ff.get_perf_metrics().get_mean_loss()))


def test_serving_this_graph_is_refused_by_name(lfm2):
    from flexflow_tpu.serving.decode_graph import PREFIX, refuse

    with pytest.raises(NotImplementedError, match="l0_attn.*no decode op"):
        refuse(lfm2, "serve()", PREFIX)
    with pytest.raises(NotImplementedError, match="gated short convolution"):
        lfm2.serve(slots=2, max_new_tokens=2)


# ------------------------------------------------ grouped heads, flash

def test_grouped_flash_path_against_the_einsum():
    """A grouped causal layer under impl='flash' takes the packed kernels
    (interpret mode here) behind the repeat of its KV heads: forward and
    the gradients of q's, k's and v's projections against impl='xla', at a
    sequence the kernels' gate takes (128)."""
    front = attn_ops.AttentionFrontEnd(
        embed_dim=64, num_heads=4, use_bias=False, rope_theta=10000.0,
        qk_norm="head", num_kv_heads=2)
    rng = np.random.default_rng(4)
    specs = front.weight_specs(64, 64, 64)
    w = {s.name: jnp.asarray(
        rng.uniform(0.5, 1.5, s.shape) if s.name.endswith("_norm")
        else rng.normal(0, 0.15, s.shape), jnp.float32) for s in specs}
    x = jnp.asarray(rng.normal(0, 1, (1, 128, 64)), jnp.float32)
    pos = jnp.tile(jnp.arange(128, dtype=jnp.int32), (1, 1))
    weight = jnp.asarray(rng.normal(0, 1, (1, 128, 64)), jnp.float32)
    op = get_op_def(OT.OP_MULTIHEAD_ATTENTION)
    import flexflow_tpu.kernels.flash_attention  # noqa: F401

    def out(w, impl):
        p = attn_ops.MultiHeadAttentionParams(front, causal=True, impl=impl)
        (y,), _ = op.forward(p, [x, x, x, pos], w, {},
                             OpContext(training=True))
        return y

    fa = sys.modules["flexflow_tpu.kernels.flash_attention"]
    called = []
    real = fa.flash_attention_packed
    fa.flash_attention_packed = lambda *a, **k: (called.append(k),
                                                 real(*a, **k))[1]
    try:
        close(out(w, "flash"), out(w, "xla"), tol=1e-5)
        got = jax.grad(lambda w: jnp.sum(out(w, "flash") * weight))(w)
    finally:
        fa.flash_attention_packed = real
    assert called and called[0]["num_heads"] == 4
    want = jax.grad(lambda w: jnp.sum(out(w, "xla") * weight))(w)
    for name in ("wq", "wk", "wv", "wo", "q_norm", "k_norm"):
        close(got[name], want[name], tol=1e-4)
    assert w["wk"].shape == (64, 32)    # two KV heads: the group is real


def test_a_window_layer_still_takes_the_einsum():
    front = attn_ops.AttentionFrontEnd(
        embed_dim=64, num_heads=4, use_bias=False, num_kv_heads=2, window=16)
    w = {s.name: jnp.ones(s.shape, jnp.float32) * 0.01
         for s in front.weight_specs(64, 64, 64)}
    x = jnp.ones((1, 128, 64), jnp.float32)
    import flexflow_tpu.kernels.flash_attention  # noqa: F401

    fa = sys.modules["flexflow_tpu.kernels.flash_attention"]
    real = fa.flash_attention_packed
    fa.flash_attention_packed = None    # a call would raise
    try:
        p = attn_ops.MultiHeadAttentionParams(front, causal=True,
                                              impl="flash")
        get_op_def(OT.OP_MULTIHEAD_ATTENTION).forward(
            p, [x, x, x], w, {}, OpContext(training=True))
    finally:
        fa.flash_attention_packed = real


# ------------------------------------------------ the config builder

def catalog_row():
    import json
    import os

    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog is not at hand")
    with open(path) as f:
        return next(r for r in map(json.loads, f)
                    if r["name"] == "LFM2-8B-A1B")["config"]


def test_config_from_the_catalog_row_verbatim():
    c = lfm2_moe_lm_config(catalog_row(), sequence_length=8192,
                           attention_impl="flash")
    assert (c.num_layers, c.hidden_size, c.num_heads, c.num_kv_heads,
            c.head_dim) == (24, 2048, 32, 8, 64)
    assert c.layer_pattern.count("conv") == 18
    assert [i for i, k in enumerate(c.layer_pattern) if k == "mha"] == [
        2, 6, 10, 14, 18, 21]
    assert (c.first_k_dense, c.intermediate_size, c.num_experts,
            c.num_experts_per_tok, c.moe_intermediate_size) == (
        2, 7168, 32, 4, 1792)
    assert c.moe_routing == dict(
        scoring="sigmoid", n_group=1, topk_group=1, norm_topk_prob=True,
        norm_topk_eps=1e-6, routed_scaling_factor=1.0, correction_bias=True,
        experts_held=None)
    assert (c.qk_norm, c.position, c.rope_theta, c.norm, c.norm_eps) == (
        "head", "rope", 1e6, "rmsnorm", 1e-5)
    assert c.tie_embeddings and c.router_bias_range == 0.0
    assert c.conv == ShortConvFrontEnd(embed_dim=2048, conv_kernel=3)
    assert c.vocab_size == 65536 and c.attention_impl == "flash"


def test_config_of_the_cut_and_what_it_refuses():
    cut = dict(catalog_row(), num_hidden_layers=6, num_dense_layers=1,
               layer_types=["conv", "full_attention", "conv", "conv", "conv",
                            "full_attention"],
               num_experts=8, experts_held=[0, 8], experts_routed=32,
               vocab_size=16384)
    c = lfm2_moe_lm_config(cut, sequence_length=8192)
    assert c.layer_pattern == ("conv", "mha", "conv", "conv", "conv", "mha")
    assert c.num_experts == 32 and c.moe_routing["experts_held"] == (0, 8)
    assert c.first_k_dense == 1 and c.vocab_size == 16384
    with pytest.raises(NotImplementedError, match="conv_bias"):
        lfm2_moe_lm_config(dict(cut, conv_bias=True), sequence_length=64)
    with pytest.raises(NotImplementedError, match="layer_types"):
        lfm2_moe_lm_config(
            dict(cut, layer_types=["conv"] * 5 + ["sliding_attention"]),
            sequence_length=64)
    untied = lfm2_moe_lm_config(dict(cut, tie_word_embeddings=False),
                                sequence_length=64)
    assert not untied.tie_embeddings


def test_layer_kinds_come_from_one_table():
    from flexflow_tpu.models import transformer

    assert set(transformer.LAYER_KINDS) == {"mha", "swa", "delta", "mamba",
                                            "conv"}
    with pytest.raises(ValueError, match="'mamba' \\| 'conv'"):
        TransformerLMConfig(num_layers=1, layer_pattern=("ssm",))
    with pytest.raises(ValueError, match="'conv' needs `conv`"):
        TransformerLMConfig(num_layers=1, layer_pattern=("conv",))
    with pytest.raises(ValueError, match="'delta' needs `delta`"):
        TransformerLMConfig(num_layers=1, layer_pattern=("delta",))


# ------------------------------------------------ fit

def test_fit_and_the_loss_falls():
    """A held share trains through fit(): the loss falls on a repeated
    batch and nothing held is dropped. The router's bias has a zero
    gradient, so Adam leaves it where it was; the layer's counters are
    state, not parameters: the step hands them on and the optimizer never
    sees them."""
    held = dict(TINY, num_experts=4, experts_held=[0, 4], experts_routed=8)
    ff = build(held, optimizer=AdamOptimizer(alpha=3e-3))
    unlike_scales(ff)
    bias = {i: np.asarray(ff._params[f"l{i}_moe"]["router_bias"])
            for i in (1, 2, 3)}
    x, y = batch_of(seed=8, batch=8)
    losses = []
    for _ in range(3):
        ff.reset_metrics()
        ff.fit(x, y, epochs=1, batch_size=BATCH, shuffle=False, verbose=False)
        losses.append(float(ff.get_perf_metrics().get_mean_loss()))
    assert np.all(np.isfinite(losses))
    assert losses[-1] < losses[0] - 0.05, losses
    for i in (1, 2, 3):
        moe = ff._state[f"l{i}_moe"]
        assert float(moe["dropped_tokens"]) == 0.0
        assert np.array_equal(
            np.asarray(ff._params[f"l{i}_moe"]["router_bias"]), bias[i])
        assert not {"assignments_total", "dropped_total", "expert_ids",
                    "dropped_tokens"} & set(ff._params[f"l{i}_moe"])
        # twelve steps of BATCH x SEQ tokens, 2 choices each: the held ones
        total = int(moe["assignments_total"]) + int(moe["dropped_total"])
        assert 0 < total < 12 * BATCH * SEQ * 2
        assert int(moe["dropped_total"]) == 0
        assert moe["assignments_total"].dtype == jnp.int32
