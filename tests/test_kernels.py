"""Pallas kernel tests (interpret mode on the CPU test backend)."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(causal):
    from flexflow_tpu.kernels.flash_attention import (
        _attn_reference,
        flash_attention,
    )

    rs = np.random.RandomState(0)
    b, h, s, d = 2, 2, 256, 32
    q = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    expected = _attn_reference(q, k, v, causal, scale)
    got = flash_attention(q, k, v, causal=causal, scale=scale,
                          block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_grad():
    from flexflow_tpu.kernels.flash_attention import (
        _attn_reference,
        flash_attention,
    )

    rs = np.random.RandomState(1)
    b, h, s, d = 1, 2, 128, 16
    q = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=64, block_k=64) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_attn_reference(q, k, v, True, 1.0 / np.sqrt(d)) ** 2)

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


def test_flash_attention_small_shape_fallback():
    from flexflow_tpu.kernels.flash_attention import flash_attention

    q = jnp.ones((1, 1, 8, 4))
    out = flash_attention(q, q, q, causal=False)
    assert out.shape == (1, 1, 8, 4)


def test_flash_attention_ragged_seq():
    """seq_k not divisible by block_k: padded tail must be masked."""
    from flexflow_tpu.kernels.flash_attention import (
        _attn_reference,
        flash_attention,
    )

    rs = np.random.RandomState(2)
    b, h, s, d = 1, 1, 320, 16
    q = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)
    for causal in (False, True):
        expected = _attn_reference(q, k, v, causal, scale)
        got = flash_attention(q, k, v, causal=causal, scale=scale,
                              block_q=128, block_k=128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   rtol=2e-5, atol=2e-5)


def test_flash_attention_cross_causal_alignment():
    """s_q != s_k causal: mask must be bottom-right aligned like sdpa_xla."""
    from flexflow_tpu.kernels.flash_attention import (
        _attn_reference,
        flash_attention,
    )

    rs = np.random.RandomState(3)
    b, h, d = 1, 2, 16
    q = jnp.asarray(rs.randn(b, h, 128, d), jnp.float32)
    k = jnp.asarray(rs.randn(b, h, 256, d), jnp.float32)
    v = jnp.asarray(rs.randn(b, h, 256, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)
    expected = _attn_reference(q, k, v, True, scale)
    got = flash_attention(q, k, v, causal=True, scale=scale,
                          block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
# (128, 128) with block 128 exercises the fused single-tile backward
# (ni == nj == 1 — the benchmark's own seq==block configuration)
@pytest.mark.parametrize(
    "sq,sk", [(256, 256), (320, 320), (128, 256), (320, 192), (128, 128)])
def test_flash_backward_matches_reference(causal, sq, sk):
    """Pallas dq/dk/dv kernels vs XLA autodiff of the reference attention,
    including ragged and cross-length causal shapes."""
    from flexflow_tpu.kernels.flash_attention import (
        _attn_reference,
        flash_attention,
    )

    if causal and sk < sq:
        pytest.skip("bottom-right causal with sk<sq leaves rows keyless")
    rs = np.random.RandomState(4)
    b, h, d = 2, 2, 16
    q = jnp.asarray(rs.randn(b, h, sq, d), jnp.float32)
    k = jnp.asarray(rs.randn(b, h, sk, d), jnp.float32)
    v = jnp.asarray(rs.randn(b, h, sk, d), jnp.float32)
    ct = jnp.asarray(rs.randn(b, h, sq, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    _, vjp_flash = jax.vjp(
        lambda q_, k_, v_: flash_attention(
            q_, k_, v_, causal=causal, scale=scale, block_q=128, block_k=128
        ), q, k, v,
    )
    _, vjp_ref = jax.vjp(
        lambda q_, k_, v_: _attn_reference(q_, k_, v_, causal, scale), q, k, v
    )
    for got, want, name in zip(vjp_flash(ct), vjp_ref(ct), "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4,
            err_msg=f"d{name} mismatch sq={sq} sk={sk} causal={causal}",
        )


def test_flash_backward_bf16():
    """bf16 inputs: backward runs in the kernel path and tracks the fp32
    reference to bf16 tolerance."""
    from flexflow_tpu.kernels.flash_attention import (
        _attn_reference,
        flash_attention,
    )

    rs = np.random.RandomState(5)
    b, h, s, d = 1, 2, 256, 32
    qf = rs.randn(b, h, s, d).astype(np.float32)
    kf = rs.randn(b, h, s, d).astype(np.float32)
    vf = rs.randn(b, h, s, d).astype(np.float32)
    scale = 1.0 / np.sqrt(d)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, scale=scale,
                            block_q=128, block_k=128).astype(jnp.float32) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(
            _attn_reference(q, k, v, True, scale).astype(jnp.float32) ** 2
        )

    g_bf16 = jax.grad(loss_flash, argnums=(0, 1, 2))(
        jnp.asarray(qf, jnp.bfloat16), jnp.asarray(kf, jnp.bfloat16),
        jnp.asarray(vf, jnp.bfloat16),
    )
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(
        jnp.asarray(qf), jnp.asarray(kf), jnp.asarray(vf)
    )
    for a, b_ in zip(g_bf16, g_ref):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b_), rtol=0.1, atol=0.5
        )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_layer_norm_matches_reference(dtype):
    """kernels/layer_norm.py fwd + bwd vs the jnp reference formula."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels.layer_norm import fused_layer_norm_or_none

    rs = np.random.RandomState(0)
    n, d = 512, 256
    x = jnp.asarray(rs.randn(2, n // 2, d), dtype)
    scale = jnp.asarray(rs.randn(d) * 0.5 + 1.0, jnp.float32)
    bias = jnp.asarray(rs.randn(d) * 0.1, jnp.float32)
    eps = 1e-5

    def ref(x, scale, bias):
        xf = x.astype(jnp.float32)
        mu = xf.mean(-1, keepdims=True)
        var = xf.var(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * scale + bias
        return y.astype(x.dtype)

    def fused(x, scale, bias):
        out = fused_layer_norm_or_none(x, scale, bias, (-1,), eps)
        assert out is not None
        return out

    tol = dict(rtol=1e-5, atol=1e-5) if dtype == "float32" else dict(
        rtol=2e-2, atol=2e-2)
    y_f = jax.jit(fused)(x, scale, bias)
    y_r = jax.jit(ref)(x, scale, bias)
    np.testing.assert_allclose(np.asarray(y_f, np.float32),
                               np.asarray(y_r, np.float32), **tol)

    g = jnp.asarray(rs.randn(2, n // 2, d), dtype)

    def loss(f):
        def inner(x, scale, bias):
            return jnp.sum(f(x, scale, bias).astype(jnp.float32)
                           * g.astype(jnp.float32))
        return inner

    gf = jax.jit(jax.grad(loss(fused), argnums=(0, 1, 2)))(x, scale, bias)
    gr = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(x, scale, bias)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)


def test_fused_layer_norm_gates_to_fallback():
    """Ragged / non-last-axis shapes return None (jnp fallback)."""
    import jax.numpy as jnp

    from flexflow_tpu.kernels.layer_norm import fused_layer_norm_or_none

    x = jnp.zeros((8, 100))  # d % 128 != 0
    s = jnp.ones((100,)); b = jnp.zeros((100,))
    assert fused_layer_norm_or_none(x, s, b, (-1,), 1e-5) is None
    x2 = jnp.zeros((8, 16, 128))
    s2 = jnp.ones((16,)); b2 = jnp.zeros((16,))
    assert fused_layer_norm_or_none(x2, s2, b2, (1,), 1e-5) is None


@pytest.mark.parametrize("h,d,sq,sk,block,causal,resident", [
    # four heads of 32 a lane block: several kv blocks, and one
    (4, 32, 256, 256, 128, False, True),
    (4, 32, 256, 256, 128, True, True),
    (4, 32, 128, 128, 128, False, True),
    (4, 32, 128, 128, 128, True, True),
    # head_dim 64 (two heads a lane block) and 128; under the causal mask
    # q block 0's sweep ends at the diagonal, before the last kv block
    (2, 64, 256, 256, 128, True, True),
    (2, 64, 256, 256, 128, False, True),
    (1, 128, 384, 384, 128, True, True),
    (1, 128, 256, 256, 128, False, True),
    # one kv block, its width a multiple of 128 lanes and not
    (2, 64, 128, 128, 128, True, True),
    (1, 128, 128, 128, 128, False, True),
    (1, 128, 200, 200, 512, True, True),
    # s_q < s_k: the causal offset (q block 0 sees three of four blocks)
    (2, 64, 128, 384, 128, True, True),
    (1, 128, 256, 512, 128, True, True),
    # a ragged key tail (and a ragged last q block), causal and not
    (2, 64, 320, 320, 128, True, True),
    (1, 128, 200, 200, 128, False, True),
    (1, 128, 192, 320, 128, True, True),
    # kv blocks of 64: the statistics as single columns, looped
    (2, 64, 256, 256, 64, True, True),
    # past the VMEM gate: kv blocks streamed through the grid
    (2, 64, 256, 256, 128, True, False),
    (1, 128, 320, 320, 128, True, False),
    (1, 128, 192, 320, 128, False, False),
])
def test_flash_packed_matches_reference(monkeypatch, h, d, sq, sk, block,
                                        causal, resident):
    """(b, s, h·d) packed layout (head selection via lane-offset index
    maps): the forward and its `lse` residual against the transposed-layout
    reference, on the kernel that sweeps a q block's kv blocks itself (K
    and V resident) and on the one that streams them through the grid."""
    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
    if not resident:
        monkeypatch.setattr(fa, "_FWD_RESIDENT_VMEM", 0)

    rs = np.random.RandomState(0)
    b = 2
    qp, kp, vp = (jnp.asarray(rs.randn(b, s, h * d), jnp.float32)
                  for s in (sq, sk, sk))
    scale = 1.0 / np.sqrt(d)

    def split(t):
        return t.reshape(b, t.shape[1], h, d).transpose(0, 2, 1, 3)

    expected, expected_lse = fa._attn_reference_lse(
        split(qp), split(kp), split(vp), causal, scale)
    np.testing.assert_allclose(
        np.asarray(expected),
        np.asarray(fa._attn_reference(split(qp), split(kp), split(vp),
                                      causal, scale)),
        rtol=2e-5, atol=2e-5)
    expected = expected.transpose(0, 2, 1, 3).reshape(b, sq, h * d)

    def packed(q, k, v):
        return fa.flash_attention_packed(
            q, k, v, num_heads=h, causal=causal, scale=scale,
            block_q=block, block_k=block)

    names = re.findall(r"name=(flash_attention_fwd\w*)",
                       str(jax.make_jaxpr(packed)(qp, kp, vp)))
    assert names == ["flash_attention_fwd"
                     + ("" if resident else "_streamed")
                     + ("_packed" if d == 128 else "_packed_grouped")]
    np.testing.assert_allclose(np.asarray(packed(qp, kp, vp)),
                               np.asarray(expected), rtol=2e-5, atol=2e-5)
    got, lse = fa._flash_fwd_packed(qp, kp, vp, h, causal, scale,
                                    block, block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)
    assert lse.shape == (b * h, sq, fa.LSE_LANES)
    np.testing.assert_array_equal(np.asarray(lse[:, :, 0]),
                                  np.asarray(lse[:, :, -1]))
    np.testing.assert_allclose(
        np.asarray(lse[:, :, 0]).reshape(b, h, sq),
        np.asarray(expected_lse), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("h,d,sq,sk,block", [
    (2, 16, 256, 256, 128),
    (2, 16, 128, 128, 128),
    # the forward's sweep (head_dim 64 and 128, the causal offset, a
    # ragged key tail) under the unchanged backward
    (2, 64, 256, 256, 128),
    (1, 128, 384, 384, 128),
    (2, 64, 128, 384, 128),
    (1, 128, 320, 320, 128),
])
def test_flash_packed_grad(h, d, sq, sk, block):
    """Packed-layout backward (the fused kernel over one tile and over
    several) against the XLA reference."""
    from flexflow_tpu.kernels.flash_attention import (
        _attn_reference,
        flash_attention_packed,
    )

    rs = np.random.RandomState(1)
    b = 1
    qp, kp, vp = (jnp.asarray(rs.randn(b, s, h * d), jnp.float32)
                  for s in (sq, sk, sk))

    def f_packed(q, k, v):
        return jnp.sum(flash_attention_packed(
            q, k, v, num_heads=h, causal=True,
            block_q=block, block_k=block) ** 2)

    def f_ref(q, k, v):
        def split(t):
            return t.reshape(b, t.shape[1], h, d).transpose(0, 2, 1, 3)

        o = _attn_reference(split(q), split(k), split(v), True,
                            1.0 / np.sqrt(d))
        return jnp.sum(o ** 2)

    g1 = jax.grad(f_packed, argnums=(0, 1, 2))(qp, kp, vp)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(qp, kp, vp)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


def _packed_grads(monkeypatch, budget, h, q, k, v, ct, causal, block):
    """(dq, dk, dv) of flash_attention_packed and the names of the backward
    kernels it ran, with the fused backward's VMEM budget at `budget`
    bytes (None: as shipped)."""
    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
    if budget is not None:
        monkeypatch.setattr(fa, "_BWD_FUSED_VMEM", budget)
    _, vjp = jax.vjp(
        lambda *a: fa.flash_attention_packed(
            *a, num_heads=h, causal=causal, block_q=block, block_k=block),
        q, k, v)
    names = re.findall(r"name=(flash_attention_bwd\w*)",
                       str(jax.make_jaxpr(vjp)(ct)))
    return vjp(ct), sorted(names)


@pytest.mark.parametrize("h,d,sq,sk,causal,dtype", [
    # several tiles, head_dim 64 grouped and 128, causal and not
    (2, 64, 256, 256, True, "float32"),
    (2, 64, 256, 256, False, "float32"),
    (1, 128, 256, 256, True, "float32"),
    (1, 128, 256, 256, False, "float32"),
    # one tile
    (2, 64, 128, 128, True, "float32"),
    (1, 128, 128, 128, True, "float32"),
    (1, 128, 128, 128, False, "float32"),
    # a ragged last block, of q and of k
    (2, 64, 320, 320, True, "float32"),
    (1, 128, 320, 320, False, "float32"),
    # s_q != s_k: the causal offset, ragged cross-attention either way
    (2, 64, 128, 384, True, "float32"),
    (1, 128, 192, 320, True, "float32"),
    (2, 64, 320, 192, False, "float32"),
    # bf16 operands
    (2, 64, 256, 256, True, "bfloat16"),
    (1, 128, 256, 256, True, "bfloat16"),
    (2, 64, 320, 320, True, "bfloat16"),
    (1, 128, 128, 384, True, "bfloat16"),
])
def test_flash_packed_fused_backward(monkeypatch, h, d, sq, sk, causal,
                                     dtype):
    """The fused packed backward (one kernel: dq, dk, dv from one build of
    each live tile) gives the split dq / dkv pair's gradients bit for bit
    (every sum runs in the pair's order), and both track the XLA
    reference's autodiff in float32."""
    from flexflow_tpu.kernels.flash_attention import _attn_reference

    rs = np.random.RandomState(7)
    b, block = 2, 128
    q, k, v, ct = (jnp.asarray(rs.randn(b, s, h * d), dtype)
                   for s in (sq, sk, sk, sq))
    family = "_packed_grouped" if d < 128 else "_packed"

    fused, names = _packed_grads(monkeypatch, None, h, q, k, v, ct, causal,
                                 block)
    assert names == ["flash_attention_bwd" + family]
    split, names = _packed_grads(monkeypatch, 0, h, q, k, v, ct, causal,
                                 block)
    assert names == ["flash_attention_bwd_dkv" + family,
                     "flash_attention_bwd_dq" + family]
    for got, want, name in zip(fused, split, "qkv"):
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            err_msg=f"d{name}: fused != split")

    def reference(q_, k_, v_):
        def heads(t):
            return (t.astype(jnp.float32)
                    .reshape(b, t.shape[1], h, d).transpose(0, 2, 1, 3))

        o = _attn_reference(heads(q_), heads(k_), heads(v_), causal,
                            1.0 / np.sqrt(d))
        return o.transpose(0, 2, 1, 3).reshape(b, sq, h * d)

    _, vjp_ref = jax.vjp(reference, q, k, v)
    tol = 1e-4 if dtype == "float32" else 4e-2
    for got, want, name in zip(fused, vjp_ref(ct.astype(jnp.float32)),
                               "qkv"):
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol, err_msg=f"d{name} against the reference")


@pytest.mark.parametrize("shape,h,dtype,expected", [
    # one 320-lane head in float32: 2.5 MiB of scratch + 2 x 2.5 of output
    ((1, 2048, 320), 1, "float32", ["flash_attention_bwd_packed"]),
    # 256 rows more: 3.1 + 2 x 2.8 MiB
    ((1, 2304, 320), 1, "float32", ["flash_attention_bwd_dkv_packed",
                                    "flash_attention_bwd_dq_packed"]),
    # gpt2-medium's heads over 16,384 tokens: 8 + 2 x 4 MiB a head pair
    ((1, 16384, 1024), 16, "bfloat16",
     ["flash_attention_bwd_dkv_packed_grouped",
      "flash_attention_bwd_dq_packed_grouped"]),
])
def test_flash_packed_backward_gate_reads_bytes(shape, h, dtype, expected):
    """The fused backward runs where a head group's dq for the whole
    sequence (float32 scratch + the output block twice) fits
    `_BWD_FUSED_VMEM` (8 MiB); past it the dq and dkv kernels run."""
    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
    x = jax.ShapeDtypeStruct(shape, dtype)

    def bwd(q, k, v, ct):
        _, vjp = jax.vjp(
            lambda *a: fa.flash_attention_packed(
                *a, num_heads=h, causal=True), q, k, v)
        return vjp(ct)

    names = re.findall(r"name=(flash_attention_bwd\w*)",
                       str(jax.make_jaxpr(bwd)(x, x, x, x)))
    assert sorted(names) == expected


@pytest.mark.parametrize("mesh_flag,megatron", [("4,1,1,1", False),
                                                ("2,2,1,1", True)])
def test_flash_and_layer_norm_per_shard_match_one_device(mesh_flag,
                                                         megatron):
    """On a multi-device mesh the ops run the flash and LayerNorm kernels
    once per shard (batch rows over `data`, heads over `model`) inside
    shard_map; the losses of a few train steps match the one-device run."""
    import sys

    from flexflow_tpu import (
        FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
    )
    from flexflow_tpu.models import TransformerLMConfig, build_transformer_lm
    from flexflow_tpu.parallel import megatron_transformer

    cfg = TransformerLMConfig(vocab_size=128, hidden_size=128, num_heads=4,
                              num_layers=1, sequence_length=128,
                              attention_impl="flash")
    rs = np.random.RandomState(0)
    x = {"tokens": rs.randint(0, 128, (4, 128)).astype(np.int32),
         "positions": np.tile(np.arange(128, dtype=np.int32), (4, 1))}
    y = rs.randint(0, 128, (4, 128, 1)).astype(np.int32)

    def losses(flag, strategy_fn):
        sys.argv = ["test", "--mesh", flag]
        config = FFConfig()
        config.batch_size = 4
        ff = FFModel(config)
        build_transformer_lm(ff, cfg, batch_size=4)
        if strategy_fn is not None:
            ff.set_strategy(strategy_fn(ff))
        ff.compile(
            optimizer=SGDOptimizer(lr=0.05),
            loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
            metrics=[MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY])
        out = []
        for _ in range(3):
            ff.reset_metrics()
            ff.fit(x, y, epochs=1, batch_size=4, shuffle=False,
                   verbose=False)
            out.append(ff.get_perf_metrics().get_mean_loss())
        return out

    one = losses("1,1,1,1", None)
    many = losses(mesh_flag, megatron_transformer if megatron else None)
    assert one[-1] < one[0]
    np.testing.assert_allclose(many, one, rtol=1e-5)


def test_kernel_bundles_reads_a_schedule(tmp_path):
    """scripts/kernel_bundles.py: bundles by loop depth and what fills
    their slots, from the text libtpu's LLO dump writes."""
    import sys

    sys.path.insert(0, "scripts")
    try:
        import kernel_bundles
    finally:
        sys.path.remove("scripts")
    dump = tmp_path / "1-flash_attention_fwd_packed.1-71-final_bundles.txt"
    dump.write_text(
        "LB: loop body\n"
        "     0   :  { %s1 = smov 0 }\n"
        "   0x1 LB: > { %v1 = vld [vmem:[#a] sm:$0xff]  ;;  "
        "%2 = vst [vmem:[#b] sm:$0xff] %v1 }\n"
        "   0x2   : >> { %v3 = vmul.f32 %v1, %v1  ;;  "
        "%v4 = vpop.f32.mrf.mxu0  ;;  %5 = vmatmul.bf16.gmra.mxu1 %v1 }\n"
        "   0x3   : >> { %v6 = vpow2.f32 %v3  ;;  "
        "%v7 = vmax.xlane.f32.xlu0 %v3 }\n"
        "   0x4 LE: > { %8 = vst [vmem:[#c] sm:$0xff] %v6 }\n")
    assert [(d, n, dict(ops))
            for d, n, ops in kernel_bundles.segments(str(dump))] == [
        (0, 1, {}),
        (1, 1, {"load": 1, "store": 1}),
        (2, 2, {"valu": 1, "pop": 1, "mxu": 1, "eup": 1, "xlu": 1}),
        (1, 1, {"store": 1}),
    ]
