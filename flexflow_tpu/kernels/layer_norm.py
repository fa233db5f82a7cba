"""Fused LayerNorm as Pallas TPU kernels.

XLA lowers layer-norm forward to a convert+reduce fusion that runs ~9x off
the HBM roofline at transformer shapes (measured 190µs for a 16.8MB
read+write on v5e — the cross-lane row reductions don't pipeline well), and
the affine epilogue in the naive jnp spelling promotes bf16 activations to
f32. These kernels do the whole thing in one VMEM pass per row block:

- forward: row mean/variance (f32), normalize, affine, cast — one HBM read
  + one write of the activation.
- backward: recomputes row stats from x (cheaper than spilling residuals),
  emits dx in one pass plus per-block partial dscale/dbias reduced by one
  tiny XLA sum outside (the reduction over rows is lane-parallel, unlike
  the forward's within-row reductions).

Reference: layer_norm.cu's Welford kernels play the same role. On non-TPU
backends the kernels run in Pallas interpret mode so tests exercise the
same path. Shapes that don't tile (ragged rows / tiny feature dims /
non-last-axis normalization) fall back to the jnp path in ops/core.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec

from .dispatch import per_shard, shards_of, spec_entries, warn_reference

_ROW_BLOCK = 256


def _row_block(n: int, d: int) -> int:
    """Row-block size capped so the kernels' f32 temporaries (~8 live
    (rb, d) buffers in the backward) stay inside Mosaic's 16MB scoped
    vmem: rb·d·4 ≤ 1MB keeps the worst case ≈8MB. The cap rounds DOWN to a
    power of two so it still divides the power-of-two-ish row counts
    transformers produce (a multiple-of-8 cap like 168 at d=1536 would
    fail n % rb for every power-of-two n and silently disable the fusion).
    d=1024 keeps the tuned rb=256; rb=256 at d=2048 overflowed scoped vmem
    on v5e (caught by scripts/cost_model_fidelity.py)."""
    cap = max(8, 262144 // max(1, d))
    cap = 1 << (cap.bit_length() - 1)  # floor to a power of two
    return min(_ROW_BLOCK, cap, n)


def _fwd_kernel(x_ref, s_ref, b_ref, y_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # (rb, d)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = xc * rstd * s_ref[...].astype(jnp.float32) + b_ref[...].astype(
        jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)


def _bwd_kernel(x_ref, s_ref, dy_ref, dx_ref, ds_ref, db_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    dyh = dy * s
    m1 = jnp.mean(dyh * xhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dyh, axis=-1, keepdims=True)
    dx_ref[...] = (rstd * (dyh - m2 - xhat * m1)).astype(dx_ref.dtype)
    # partial reductions broadcast over 8 sublanes (Mosaic's minimum block
    # sublane count); the caller reads row 0 of each block
    ds = jnp.sum(dy * xhat, axis=0, keepdims=True)
    db = jnp.sum(dy, axis=0, keepdims=True)
    ds_ref[0] = jnp.broadcast_to(ds, ds_ref[0].shape)
    db_ref[0] = jnp.broadcast_to(db, db_ref[0].shape)


def _call_fwd(x2, scale2, bias2, eps):
    n, d = x2.shape
    rb = _row_block(n, d)
    grid = (n // rb,)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rb, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x2.dtype),
        interpret=jax.default_backend() != "tpu",
        name="layer_norm_fwd",
    )(x2, scale2, bias2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_ln(x2, scale, bias, eps):
    return _call_fwd(x2, scale.reshape(1, -1), bias.reshape(1, -1), eps)


def _fused_ln_fwd(x2, scale, bias, eps):
    return _fused_ln(x2, scale, bias, eps), (x2, scale)


def _fused_ln_bwd(eps, res, dy):
    x2, scale = res
    n, d = x2.shape
    rb = _row_block(n, d)
    grid = (n // rb,)
    dx, ds_part, db_part = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((rb, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rb, d), lambda i: (i, 0)),
            pl.BlockSpec((1, 8, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 8, d), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x2.dtype),
            jax.ShapeDtypeStruct((grid[0], 8, d), jnp.float32),
            jax.ShapeDtypeStruct((grid[0], 8, d), jnp.float32),
        ],
        interpret=jax.default_backend() != "tpu",
        name="layer_norm_bwd",
    )(x2, scale.reshape(1, -1), dy)
    return dx, ds_part[:, 0].sum(axis=0), db_part[:, 0].sum(axis=0)


_fused_ln.defvjp(_fused_ln_fwd, _fused_ln_bwd)


def fused_layer_norm_or_none(x, scale, bias, axes, eps, mesh=None,
                             spec=None):
    """Fused path when the shape tiles: last-axis-only normalization,
    feature dim a multiple of 128, rows divisible by the row block.
    Returns None when the caller should use the jnp fallback (with a
    KernelFallbackWarning on a TPU). On a multi-device `mesh` the kernels
    run per shard of `spec` (x's placement), so the gates read the
    per-shard shape."""
    ndim = x.ndim
    entries = spec_entries(spec, ndim)
    shards = [shards_of(mesh, e) for e in entries]
    d = x.shape[-1] // shards[-1]
    n = math.prod(s // k for s, k in zip(x.shape[:-1], shards))
    # rows must divide into 8-sublane-aligned blocks: `n % rb` alone is
    # vacuous for n < rb (n % n == 0) and a 12-row or 100-row block would
    # fail Mosaic's 8-sublane tiling on real TPU (interpret-mode CPU tests
    # can't catch that)
    rb = _row_block(n, d)
    gate = None
    if tuple(a % ndim for a in axes) != (ndim - 1,):
        gate = f"normalized axes {tuple(axes)} are not the last axis"
    elif any(s % k for s, k in zip(x.shape, shards)):
        gate = f"shape does not divide over {spec}"
    elif entries[-1] is not None:
        gate = f"normalized axis sharded over {entries[-1]!r}"
    elif d % 128 != 0:
        gate = f"feature dim {d} % 128 != 0"
    elif n < 8 or rb % 8 != 0 or n % rb != 0:
        gate = f"{n} rows do not tile into 8-aligned blocks of {rb}"
    if gate is not None:
        warn_reference("layer_norm", x.shape, gate)
        return None

    def run(xl, sc, bi):
        return _fused_ln(xl.reshape(n, d), sc, bi, float(eps)).reshape(
            xl.shape)

    p_x = PartitionSpec(*entries)
    return per_shard(run, mesh, (p_x, PartitionSpec(), PartitionSpec()),
                     p_x)(x, scale, bias)
