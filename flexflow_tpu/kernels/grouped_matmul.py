"""Grouped matmul: rows sorted by group, one weight matrix a group.

`grouped_matmul(lhs (m, k), rhs (g, k, n), group_sizes (g,))` multiplies
the first `group_sizes[0]` rows of `lhs` by `rhs[0]`, the next
`group_sizes[1]` by `rhs[1]`, and so on; rows past the sum give zeros. It
is the matmul of a token-routed expert layer after its dispatch has sorted
the assignments by expert: no capacity and no padding, so nothing is
dropped.

Two implementations of the one contract:

- the Pallas grouped matmul that ships with JAX
  (`jax.experimental.pallas.ops.tpu.megablox`: `gmm` forward and dX, the
  transposed `tgmm` for dW, joined by its own `custom_vjp`), on one TPU
  device at bf16 shapes its tiles divide. On the chip at OLMoE's shapes
  its nine matmuls of a step take three quarters of the time of XLA's
  (PERF.md section 6, PR 27), so it is the path;
- `jax.lax.ragged_dot`, with JAX's own transposes for dX and dW: every
  other case (the CPU, other dtypes, a mesh of several devices), and the
  reference the tests hold the kernel to. On a TPU taking it is said with
  a `KernelFallbackWarning`. XLA lowers it to Mosaic kernels of its own
  (`ragged-dot-*` in a trace); the Pallas calls are `gmm*` and `tgmm*`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .dispatch import warn_reference

# rows, contraction and columns of a tile. On a v5e at (131072, 2048) x
# (64, 2048, 1024): 512 x 1024 x 1024 is the fastest of those tried and
# 1024 rows no longer fit the 16 MiB of scoped VMEM (my chip run, PR 27)
TILING = (512, 1024, 1024)


def padded_rows(m: int) -> int:
    """The row count the Pallas kernel runs for m rows: the next multiple
    of its row tile (of 128 below one tile). A serving step's few hundred
    assignments are padded with zero rows, which lie past the groups' sum
    and give zeros."""
    tile = min(TILING[0], -(-m // 128) * 128)
    return -(-m // tile) * tile


def pallas_tiling(lhs, rhs, mesh=None):
    """(tiling, None) where the Pallas kernel takes these operands (lhs
    padded to `padded_rows`), else (None, why not)."""
    m, k = padded_rows(lhs.shape[0]), lhs.shape[1]
    n = rhs.shape[2]
    if mesh is not None and mesh.size > 1:
        return None, "a mesh of several devices (the kernel is not sharded)"
    if lhs.dtype != jnp.bfloat16 or rhs.dtype != jnp.bfloat16:
        return None, f"operands are {lhs.dtype} x {rhs.dtype}, not bfloat16"
    tiling = tuple(min(t, s) for t, s in zip(TILING, (m, k, n)))
    if any(s % t or t % 128 for t, s in zip(tiling, (m, k, n))):
        return None, f"tiles {tiling} do not divide ({m}, {k}, {n})"
    return tiling, None


def grouped_matmul_reference(lhs, rhs, group_sizes):
    """(m, n) in lhs's dtype. The result type is left to the operands': a
    float32 result of bf16 operands is written to HBM in float32 and
    converted in a pass of its own, and its cotangent then makes float32
    operands of the backward's matmuls (the MXU accumulates in float32
    either way)."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32))


def grouped_matmul_pallas(lhs, rhs, group_sizes, tiling,
                          interpret: bool = False):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    return gmm(lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype, tiling,
               None, None, False, interpret)


def grouped_matmul(lhs, rhs, group_sizes, mesh=None):
    """(m, n) in lhs's dtype, accumulated in float32."""
    if jax.default_backend() != "tpu":
        return grouped_matmul_reference(lhs, rhs, group_sizes)
    tiling, gate = pallas_tiling(lhs, rhs, mesh)
    if tiling is None:
        warn_reference("grouped_matmul", (lhs.shape, rhs.shape), gate)
        return grouped_matmul_reference(lhs, rhs, group_sizes)
    m = lhs.shape[0]
    if padded_rows(m) != m:
        lhs = jnp.pad(lhs, ((0, padded_rows(m) - m), (0, 0)))
    return grouped_matmul_pallas(lhs, rhs, group_sizes, tiling)[:m]
