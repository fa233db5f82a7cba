"""Long-context leg (SURVEY §5 — capability the reference lacks): ring
attention over the seq axis and the flash kernel's online-softmax path must
agree with the XLA reference at 4k sequence on the CPU mesh. The real-chip
throughput leg is bench.py's seq-4096 secondary metric.

Round 7 widens this into the long-context roofline matrix: the
double-buffered flash-block ring (forward AND gradient, causal and
bidirectional, 2- and 4-shard seq axes, non-divisible s_loc, overlap
on/off), the relayout-free narrow-head packed kernels, and the decomposed
collective matmul — all on the CPU `shard_map` mesh so tier-1 exercises
the exact schedules the TPU runs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh


def _mesh_seq8():
    devs = np.array(jax.devices()[:8]).reshape(1, 1, 8)
    return Mesh(devs, ("data", "model", "seq"))


def _mesh_seq(n):
    from flexflow_tpu.machine import MeshShape, build_mesh

    return build_mesh(MeshShape((1, 1, n, 1),
                                ("data", "model", "seq", "pipe")))


def test_ring_vs_flash_vs_reference_seq4k():
    from flexflow_tpu.kernels.flash_attention import (
        _attn_reference,
        flash_attention,
    )
    from flexflow_tpu.parallel.ring_attention import ring_attention

    rs = np.random.RandomState(0)
    b, h, s, d = 1, 1, 4096, 8
    q = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    ref = np.asarray(_attn_reference(q, k, v, True, scale))
    flash = np.asarray(flash_attention(q, k, v, causal=True, scale=scale,
                                       block_q=512, block_k=512))
    np.testing.assert_allclose(flash, ref, rtol=2e-4, atol=2e-4)

    mesh = _mesh_seq8()
    ring = np.asarray(jax.jit(
        lambda q, k, v: ring_attention(q, k, v, causal=True, scale=scale,
                                       mesh=mesh)
    )(q, k, v))
    np.testing.assert_allclose(ring, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n_shards", [2, 4])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("overlap", [True, False])
def test_ring_forward_and_grad_parity(n_shards, causal, overlap):
    """Ring attention (flash-block body, causal skip, double-buffered
    hops) vs the dense reference: forward and gradients, on a seq axis of
    2 and 4 shards with a NON-divisible-by-anything-clean s_loc (s=24·n →
    s_loc=24: not a lane multiple, not a power of two)."""
    from flexflow_tpu.ops.attention import sdpa_xla
    from flexflow_tpu.parallel.ring_attention import ring_attention

    mesh = _mesh_seq(n_shards)
    rs = np.random.RandomState(n_shards)
    b, h, d = 2, 2, 8
    s = 24 * n_shards
    q = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    def ring(q, k, v):
        return ring_attention(q, k, v, causal=causal, scale=scale,
                              mesh=mesh, overlap=overlap)

    expected = np.asarray(sdpa_xla(q, k, v, causal=causal, scale=scale))
    got = np.asarray(jax.jit(ring)(q, k, v))
    np.testing.assert_allclose(got, expected, rtol=2e-5, atol=2e-5)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(sdpa_xla(q, k, v, causal=causal, scale=scale) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


def test_ring_flash_block_path_seq512():
    """s_loc = 128 clears the flash kernel's shape gate, so the per-block
    attention runs the REAL Pallas online-softmax kernel (interpret mode
    on CPU) inside shard_map — forward and gradient vs the dense
    reference."""
    from flexflow_tpu.ops.attention import sdpa_xla
    from flexflow_tpu.parallel.ring_attention import ring_attention

    mesh = _mesh_seq(4)
    rs = np.random.RandomState(7)
    b, h, s, d = 1, 1, 512, 8
    q = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rs.randn(b, h, s, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    def ring(q, k, v):
        return ring_attention(q, k, v, causal=True, scale=scale, mesh=mesh)

    expected = np.asarray(sdpa_xla(q, k, v, causal=True, scale=scale))
    got = np.asarray(jax.jit(ring)(q, k, v))
    np.testing.assert_allclose(got, expected, rtol=2e-4, atol=2e-4)

    g = jax.jit(jax.grad(lambda q, k, v: jnp.sum(ring(q, k, v) ** 2),
                         argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(
            sdpa_xla(q, k, v, causal=True, scale=scale) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("shape,causal,blocks", [
    ((1, 128, 2, 64), True, (512, 512)),   # hpb=2, single kv tile
    ((1, 256, 2, 64), True, (128, 128)),   # hpb=2, online-softmax path
    ((2, 128, 4, 32), False, (512, 512)),  # hpb=4
    ((1, 128, 3, 40), True, (512, 512)),   # 128 % 40 != 0 → full-width
    ((1, 200, 2, 64), True, (128, 128)),   # ragged kv tail
    ((1, 384, 2, 64), True, (128, 128)),   # three kv blocks to the diagonal
    ((1, 256, 2, 64), False, (128, 128)),  # the whole sweep, unmasked
    ((1, 328, 4, 32), True, (128, 128)),   # hpb=4, ragged, several blocks
])
def test_narrow_head_packed_kernel_parity(shape, causal, blocks):
    """The grouped narrow-head packed path (head_dim < 128: head-GROUP
    lane blocks + in-kernel static head loop) vs the transposed-layout
    kernels, forward AND backward, in interpret mode — the relayout-free
    path the flagship's head_dim-64 model now takes."""
    from flexflow_tpu.kernels.flash_attention import (
        _packed_heads_per_block,
        flash_attention,
        flash_attention_packed,
    )

    b, s, h, d = shape
    bq, bk = blocks
    assert _packed_heads_per_block(d, h) > 1  # the grouped path, not 1-head
    e = h * d
    rs = np.random.RandomState(d)
    q = jnp.asarray(rs.randn(b, s, e), jnp.float32)
    k = jnp.asarray(rs.randn(b, s, e), jnp.float32)
    v = jnp.asarray(rs.randn(b, s, e), jnp.float32)

    def packed(q, k, v):
        return flash_attention_packed(q, k, v, num_heads=h, causal=causal,
                                      block_q=bq, block_k=bk)

    def ref(q, k, v):
        def split(t):
            return t.reshape(b, s, h, d).transpose(0, 2, 1, 3)

        out = flash_attention(split(q), split(k), split(v), causal=causal,
                              block_q=bq, block_k=bk)
        return out.transpose(0, 2, 1, 3).reshape(b, s, e)

    np.testing.assert_allclose(np.asarray(packed(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               rtol=2e-4, atol=2e-4)
    g_p = jax.grad(lambda *a: jnp.sum(packed(*a) ** 2),
                   argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2),
                   argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_p, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("overlap", [True, False])
def test_allgather_matmul_parity(overlap):
    """Decomposed all_gather→matmul (parallel/ops.allgather_matmul): the
    overlapped block-rotation schedule must equal the gathered matmul,
    values and gradients."""
    from flexflow_tpu.machine import MeshShape, build_mesh
    from flexflow_tpu.parallel.ops import allgather_matmul

    mesh = build_mesh(MeshShape((2, 4, 1, 1),
                                ("data", "model", "seq", "pipe")))
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(8, 16, 64), jnp.float32)
    w = jnp.asarray(rs.randn(64, 32), jnp.float32)
    ref = np.asarray(jnp.dot(x, w))
    got = np.asarray(jax.jit(lambda x, w: allgather_matmul(
        x, w, mesh=mesh, overlap=overlap))(x, w))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    g = jax.jit(jax.grad(lambda x, w: jnp.sum(allgather_matmul(
        x, w, mesh=mesh, overlap=overlap) ** 2), argnums=(0, 1)))(x, w)
    g_ref = jax.grad(lambda x, w: jnp.sum(jnp.dot(x, w) ** 2),
                     argnums=(0, 1))(x, w)
    for a, b_ in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-4)


def test_ablation_flags_reach_the_op(monkeypatch):
    """`--no-overlap-collectives` must flip the COMPILED schedule, not
    just the cost model's pricing: the flag flows FFConfig → OpContext →
    the attention op's ring dispatch; and impl="flash" runs the packed
    kernels. Captured at the op seam so the test is cheap and pins the
    plumbing."""
    from flexflow_tpu.executor import OpContext
    from flexflow_tpu.ops.attention import (
        AttentionFrontEnd, MultiHeadAttentionParams, _mha_forward,
    )

    seen = {}

    def fake_ring(q, k, v, *, causal, scale, mesh, overlap):
        seen["ring_overlap"] = overlap
        return jnp.zeros_like(q)

    def fake_packed(q, k, v, *, num_heads, causal, scale):
        seen["layout"] = "packed"
        return jnp.zeros_like(q)

    # importlib: the kernels package re-exports `flash_attention` the
    # function, which shadows the submodule on attribute-style imports
    import importlib

    fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")
    ra = importlib.import_module("flexflow_tpu.parallel.ring_attention")

    # the op imports the seams at call time
    monkeypatch.setattr(ra, "ring_attention", fake_ring)
    monkeypatch.setattr(fa, "flash_attention_packed", fake_packed)

    E, H = 16, 2
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(2, 8, E), jnp.float32)
    w = {n: jnp.asarray(rs.randn(E, E), jnp.float32)
         for n in AttentionFrontEnd.kernels}
    w.update({n: jnp.zeros((E,), jnp.float32)
              for n in ("bq", "bk", "bv", "bo")})

    for impl, ctx_kw, expect in (
        ("ring", {"overlap_collectives": False}, ("ring_overlap", False)),
        ("ring", {"overlap_collectives": True}, ("ring_overlap", True)),
        ("flash", {}, ("layout", "packed")),
    ):
        seen.clear()
        p = MultiHeadAttentionParams(AttentionFrontEnd(E, H), impl=impl)
        _mha_forward(p, (x, x, x), w, None, OpContext(**ctx_kw))
        key, val = expect
        assert seen.get(key) == val, (impl, ctx_kw, seen)

    # and the FFConfig flag parses into the field the executor forwards
    from flexflow_tpu import FFConfig

    c = FFConfig()
    c.parse_args(["--no-overlap-collectives"])
    assert c.overlap_collectives is False
