"""The gated short convolution's middle as a Pallas kernel pair
(kernels/short_conv.py) against the jnp form over `causal_conv`
(`ShortConvFrontEnd.conv`), on the CPU in interpret mode: the forward, the
backward kernel's two gradients and the op's three, block edges, rows
that must not see each other, the gate's refusals, and the kernels per shard
of a four-device plan. What Mosaic makes of them at the cell's shape is
tests/test_chip_compile.py's.
"""

import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from flexflow_tpu import (
    FFConfig, FFModel, LossType, SGDOptimizer,
)
from flexflow_tpu.fftype import OperatorType as OT
from flexflow_tpu.kernels import short_conv as kernel
from flexflow_tpu.kernels.dispatch import KernelFallbackWarning
from flexflow_tpu.ops import short_conv as op
from flexflow_tpu.ops.base import OpContext, get_op_def
from flexflow_tpu.ops.short_conv import ShortConvFrontEnd, ShortConvParams


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 64 tokens x 128 channels, so a few hundred tokens are
    several blocks to the interpreter."""
    monkeypatch.setattr(kernel, "_TOKEN_BLOCK", 64)
    monkeypatch.setattr(kernel, "_CHANNEL_BLOCK", 128)


@pytest.fixture
def forced(monkeypatch):
    """The op told it is on a TPU: the gate lets the kernels in, and they
    run in interpret mode because JAX is still on the CPU. Returns the
    list of `bcx` shapes the forward kernel was called on, and checks at
    the end that the backward kernel was called on the same."""
    calls, back = [], []
    forward, backward = kernel.forward, kernel.backward

    def spy_forward(bcx, taps):
        calls.append(bcx.shape)
        return forward(bcx, taps)

    def spy_backward(bcx, taps, dy):
        back.append(bcx.shape)
        return backward(bcx, taps, dy)

    monkeypatch.setattr(op, "_backend", lambda: "tpu")
    monkeypatch.setattr(kernel, "forward", spy_forward)
    monkeypatch.setattr(kernel, "backward", spy_backward)
    yield calls
    assert sorted(back) == sorted(calls)


def _operands(rows, tokens, channels, taps, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(0, 1, (3, rows, tokens, channels)), dtype),
            jnp.asarray(rng.uniform(-0.6, 0.6, (taps, channels)),
                        jnp.float32),
            jnp.asarray(rng.normal(0, 1, (rows, tokens, channels)), dtype))


def _kernels(bcx, taps, dy):
    """(y, d_bcx, d_taps) of the two kernels; the y the backward writes
    again for dW_out is the forward's to the bit."""
    y = kernel.forward(bcx, taps)
    d_bcx, d_taps, again = kernel.backward(bcx, taps, dy)
    assert again.dtype == y.dtype
    assert np.array_equal(np.asarray(again, np.float32),
                          np.asarray(y, np.float32), equal_nan=True)
    assert d_taps.shape == (1, *taps.shape)     # a shard's sum over its rows
    return y, d_bcx, d_taps[0]


def _both(bcx, taps, dy):
    """((y, d_bcx, d_taps) of the kernels, the same of the jnp form)."""
    front = ShortConvFrontEnd(bcx.shape[-1], taps.shape[0])
    y, vjp = jax.vjp(lambda b, t: front.conv({"conv": t}, b), bcx, taps)
    return _kernels(bcx, taps, dy), (y, *vjp(dy))


def _close(got, want, dtype):
    """Float32: the same sums in another order. bf16: y is the same
    roundings of the same float32 values; the gradients round once where
    autodiff rounds du and the product after it."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * np.abs(want).max())


# one block; three blocks, a tap across each edge; three blocks and a
# ragged fourth of 8 tokens: the last block hangs over the row's end
@pytest.mark.parametrize("tokens", [64, 192, 200])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kernels_against_the_jnp_form(small_blocks, dtype, tokens):
    bcx, taps, dy = _operands(2, tokens, 256, 3, dtype)
    got, want = _both(bcx, taps, dy)
    assert got[0].dtype == dtype and got[1].dtype == dtype
    assert got[1].shape == bcx.shape and got[2].shape == taps.shape
    for g, w in zip(got, want):
        _close(g, w, dtype)
    if dtype == jnp.bfloat16:
        # the forward's roundings are the jnp form's: bf16 u, float32 sum,
        # one rounding of y (an ulp where the CPU fuses a multiply-add)
        y, y_want = (np.asarray(a, np.float32) for a in (got[0], want[0]))
        assert np.mean(y == y_want) > 0.99


@pytest.mark.parametrize("taps", [1, 2, 4, 9])
def test_every_tap_count_the_gate_lets_in(small_blocks, taps):
    bcx, w, dy = _operands(1, 136, 128, taps, jnp.float32, seed=taps)
    assert kernel.refusal(136, 128, taps, jnp.float32) is None
    for g, want in zip(*_both(bcx, w, dy)):
        _close(g, want, jnp.float32)


def test_the_blocks_the_cell_runs_take_a_ragged_row():
    """The module's own block sizes: 520 tokens are a block of 512 and 8
    tokens of a second."""
    bcx, taps, dy = _operands(1, 520, 128, 3, jnp.bfloat16, seed=5)
    assert kernel._blocks(520, 128, jnp.bfloat16) == (512, 128, 16)
    assert kernel._blocks(8192, 2048, jnp.bfloat16) == (512, 512, 16)
    assert kernel._blocks(24, 384, jnp.float32) == (32, 384, 8)
    assert kernel._blocks(8192, 2048, jnp.float32) == (256, 512, 8)
    for g, want in zip(*_both(bcx, taps, dy)):
        _close(g, want, jnp.bfloat16)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_a_row_starts_from_an_empty_window_both_ways(small_blocks, dtype):
    """Row 0's last two tokens are NaN in B, C, x and dy: nothing of them
    reaches row 1, whose y, d_bcx are those of the clean operands to the
    bit; and within row 0 the NaN stays behind the tokens it follows in y
    and ahead of the tokens it precedes in du."""
    bcx, taps, dy = _operands(2, 128, 128, 3, dtype, seed=7)
    bad = bcx.at[:, 0, -2:].set(jnp.nan)
    bad_dy = dy.at[0, -2:].set(jnp.nan)
    clean = _kernels(bcx, taps, dy)
    y, d_bcx, d_taps = _kernels(bad, taps, bad_dy)
    for got, want in ((y[1], clean[0][1]), (d_bcx[:, 1], clean[1][:, 1])):
        assert np.array_equal(np.asarray(got, np.float32),
                              np.asarray(want, np.float32))
    assert np.array_equal(np.asarray(y[0, :-2], np.float32),
                          np.asarray(clean[0][0, :-2], np.float32))
    # du reaches two tokens back from the first poisoned one
    assert np.all(np.isfinite(np.asarray(d_bcx[:, 0, :-4], np.float32)))
    assert np.all(np.isnan(np.asarray(d_bcx[:, 0, -2:], np.float32)))
    assert np.all(np.isnan(np.asarray(d_taps)))    # a sum over every row


@pytest.mark.parametrize("why,tokens,channels,taps,dtype", [
    ("activations of float16", 64, 128, 3, jnp.float16),
    ("96 channels % 128", 64, 96, 3, jnp.bfloat16),
    ("60 tokens % 8", 60, 128, 3, jnp.bfloat16),
    ("10 taps reach further back than the 8 tokens", 64, 128, 10,
     jnp.float32),
])
def test_the_shape_gate_refuses_by_name(why, tokens, channels, taps, dtype):
    assert why in kernel.refusal(tokens, channels, taps, dtype)


def _front(channels=128, taps=3, seed=2, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    front = ShortConvFrontEnd(embed_dim=channels, conv_kernel=taps)
    w = {"w_in": rng.normal(0, channels ** -0.5, (channels, 3, channels)),
         "conv": rng.uniform(-0.6, 0.6, (taps, channels)),
         "w_out": rng.normal(0, channels ** -0.5, (channels, channels))}
    return front, {k: jnp.asarray(v, dtype) for k, v in w.items()}


def _op_grads(front, w, x, r):
    def loss(w, x):
        (y,), _ = get_op_def(OT.OP_SHORT_CONV).forward(
            ShortConvParams(front), [x], w, {}, OpContext(training=True))
        return jnp.sum(y.astype(jnp.float32) * r), y

    (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
        w, x)
    return y, grads


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_op_through_the_kernels(small_blocks, forced, monkeypatch, dtype):
    """The whole op, both projections round the kernels: y, dw_in, the
    taps' gradient, dw_out and dx are the jnp form's."""
    front, w = _front(dtype=dtype)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(0, 1, (2, 72, 128)), dtype)
    r = jnp.asarray(rng.normal(0, 1, (2, 72, 128)), jnp.float32)
    y, (dw, dx) = _op_grads(front, w, x, r)
    assert forced == [(3, 2, 72, 128)]
    monkeypatch.setattr(op, "_backend", lambda: "cpu")
    y_want, (dw_want, dx_want) = _op_grads(front, w, x, r)
    assert len(forced) == 1
    _close(y, y_want, dtype)
    _close(dx, dx_want, dtype)
    for name in ("w_in", "conv", "w_out"):
        assert dw[name].dtype == dtype
        _close(dw[name], dw_want[name], dtype)


def _mesh(shape):
    return jax.sharding.Mesh(
        np.array(jax.devices()[:int(np.prod(shape))]).reshape(shape),
        ("data", "model"))


@pytest.mark.parametrize("why,backend,mesh,ctx,shape,taps,dtype", [
    ("backend 'cpu' is no TPU", "cpu", None, {}, (3, 2, 64, 128), 3,
     jnp.bfloat16),
    ("neither rows nor channels are split over the mesh", "tpu", (2, 2), {},
     (3, 2, 64, 256), 3, jnp.bfloat16),
    # 3 rows do not divide over two devices, so the rows are not split
    ("neither rows nor channels are split over the mesh", "tpu", (2, 2),
     {"out_spec": PartitionSpec("data")}, (3, 3, 64, 256), 3, jnp.bfloat16),
    # a shard's channels decide, not the layer's
    ("64 channels % 128", "tpu", (2, 2),
     {"weight_axes": {"conv": PartitionSpec(None, "model")}},
     (3, 2, 64, 128), 3, jnp.bfloat16),
    ("60 tokens % 8", "tpu", None, {}, (3, 2, 60, 128), 3, jnp.float32),
    ("12 taps reach further back", "tpu", None, {}, (3, 2, 64, 128), 12,
     jnp.float32),
    ("activations of float16", "tpu", None, {}, (3, 2, 64, 128), 3,
     jnp.float16),
])
def test_who_takes_the_kernels_is_read_off_the_call(
        monkeypatch, why, backend, mesh, ctx, shape, taps, dtype):
    monkeypatch.setattr(op, "_backend", lambda: backend)
    ctx = OpContext(mesh=_mesh(mesh) if mesh else None, **ctx)
    axes, said = op._kernel_plan(ctx, shape, taps, dtype)
    assert axes is None and why in said


def test_the_plan_hands_the_kernels_its_axes(monkeypatch):
    monkeypatch.setattr(op, "_backend", lambda: "tpu")
    ctx = OpContext(mesh=_mesh((2, 2)), out_spec=PartitionSpec("data"),
                    weight_axes={"conv": PartitionSpec(None, "model")})
    assert op._kernel_plan(ctx, (3, 4, 64, 256), 3, jnp.bfloat16) == (
        ("data", "model"), None)
    assert op._kernel_plan(OpContext(), (3, 4, 64, 256), 3, jnp.float32) == (
        (None, None), None)


def test_a_refused_call_on_a_tpu_says_so_and_takes_the_jnp_form(monkeypatch):
    front, w = _front(channels=32)
    x = jnp.ones((2, 16, 32), jnp.float32)
    (want,), _ = get_op_def(OT.OP_SHORT_CONV).forward(
        ShortConvParams(front), [x], w, {}, OpContext(training=True))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.warns(KernelFallbackWarning, match="32 channels % 128"):
        (y,), _ = get_op_def(OT.OP_SHORT_CONV).forward(
            ShortConvParams(front), [x], w, {}, OpContext(training=True))
    assert np.array_equal(np.asarray(y), np.asarray(want))
    monkeypatch.undo()
    with warnings.catch_warnings():    # the CPU's own path is silent
        warnings.simplefilter("error", KernelFallbackWarning)
        get_op_def(OT.OP_SHORT_CONV).forward(
            ShortConvParams(front), [x], w, {}, OpContext(training=True))


def _sconv_model(mesh_axes, plan, channels=512):
    """tests/test_lfm2_moe.py's `_sconv_model` at a width whose shards the
    kernels take: 128 channels a device under the channel split."""
    sys.argv = ["t", "--seed", "0"]
    config = FFConfig()
    config.mesh_axis_sizes = mesh_axes
    config.batch_size = 4
    ff = FFModel(config)
    x = ff.create_tensor((4, 16, channels), name="x")
    front = ShortConvFrontEnd(embed_dim=channels)
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


@pytest.mark.parametrize("mesh,plan,shard", [
    ((4, 1, 1, 1), "dp", (3, 1, 16, 512)),
    ((1, 4, 1, 1), "channel", (3, 4, 16, 128)),
])
def test_the_kernels_per_shard_train_as_one_device_does(
        forced, monkeypatch, mesh, plan, shard):
    """`test_short_conv_plans_match_one_device` with the kernels in: four
    devices, each running them on its rows (dp) or its 128 channels (the
    channel split, `w_out`'s partial sums still the plan's psum), reach
    the weights one device reaches through the jnp form."""
    rs = np.random.RandomState(0)
    x = rs.randn(8, 16, 512).astype(np.float32)
    y = rs.randn(8, 16, 1).astype(np.float32)
    four = _sconv_model(mesh, plan)
    assert four.mesh.devices.size == 4
    four.fit(x, y, epochs=2, batch_size=4, shuffle=False, verbose=False)
    assert forced and set(forced) == {shard}
    traced = len(forced)
    monkeypatch.setattr(op, "_backend", lambda: "cpu")
    one = _sconv_model((1, 1, 1, 1), "dp")
    one.fit(x, y, epochs=2, batch_size=4, shuffle=False, verbose=False)
    assert len(forced) == traced
    for name in ("w_in", "conv", "w_out"):
        np.testing.assert_allclose(
            np.asarray(four._params["mix"][name]),
            np.asarray(one._params["mix"][name]), rtol=2e-5, atol=2e-6)
