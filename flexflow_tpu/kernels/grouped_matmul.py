"""Grouped matmul: rows sorted by group, one weight matrix a group.

`grouped_matmul(lhs (m, k), rhs (g, k, n), group_sizes (g,))` multiplies
the first `group_sizes[0]` rows of `lhs` by `rhs[0]`, the next
`group_sizes[1]` by `rhs[1]`, and so on; rows past the sum give zeros
under `ragged_dot` and are left UNWRITTEN by the Pallas kernel, forward and
dX alike (whoever sorts rows there masks what it reads of them:
ops/moe.py, `picked` and `_gather_sorted`'s `live`). It
is the matmul of a token-routed expert layer after its dispatch has sorted
the assignments by expert: no capacity and no padding, so nothing is
dropped.

Two implementations of the one contract:

- the Pallas grouped matmul that ships with JAX
  (`jax.experimental.pallas.ops.tpu.megablox`: `gmm` forward and dX, the
  transposed `tgmm` for dW, joined by its own `custom_vjp`), on one TPU
  device at bf16 shapes that whole 128s tile. On the chip at OLMoE's shapes
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

# rows, contraction and columns of a tile, at the most. On a v5e at
# (131072, 2048) x (64, 2048, 1024): 512 x 1024 x 1024 is the fastest of
# those tried and 1024 rows no longer fit the 16 MiB of scoped VMEM (my
# chip run, PR 27)
TILING = (512, 1024, 1024)
# the row tile of a shape these do not divide, where a group has fewer rows
# than that in the mean (a decode step's few assignments an expert): the
# kernel multiplies a whole row tile by the weights of every group with a
# row in it, so at (1024, 4096) x (40, 4096, 1280) and 130 routed rows
# 128 x 1024 x 640 takes 0.70 ms, 512 x 1024 x 640 1.19 and XLA's
# ragged-dot 1.92 (my chip run, PR 33). Shapes TILING divides keep it: the
# cells that run them were measured at it
SMALL_ROW_TILE = 128


def padded_rows(m: int, tile: int = TILING[0]) -> int:
    """The row count the Pallas kernel runs for m rows: the next multiple
    of its row tile (of 128 below one tile). A serving step's few hundred
    assignments are padded with zero rows, which lie past the groups'
    sum."""
    tile = min(tile, -(-m // 128) * 128)
    return -(-m // tile) * tile


def _tile(limit: int, size: int) -> int:
    """The largest multiple of 128, `limit` at the most, that divides
    `size` (640 for 1,280 under 1,024); 0 where there is none."""
    for tile in range(min(limit, size) // 128 * 128, 0, -128):
        if size % tile == 0:
            return tile
    return 0


def pallas_tiling(lhs, rhs, mesh=None):
    """(tiling, None) where the Pallas kernel takes these operands (lhs
    padded to whole row tiles), else (None, why not)."""
    (m, k), groups, n = lhs.shape, rhs.shape[0], rhs.shape[2]
    if mesh is not None and mesh.size > 1:
        return None, "a mesh of several devices (the kernel is not sharded)"
    if lhs.dtype != jnp.bfloat16 or rhs.dtype != jnp.bfloat16:
        return None, f"operands are {lhs.dtype} x {rhs.dtype}, not bfloat16"
    tiles = _tile(TILING[1], k), _tile(TILING[2], n)
    if not all(tiles):
        return None, (f"whole 128s under {TILING[1:]} do not divide "
                      f"({k}, {n})")
    rows = TILING[0]
    if (tiles != (min(TILING[1], k), min(TILING[2], n))
            and m // groups < SMALL_ROW_TILE):
        rows = SMALL_ROW_TILE
    return (min(rows, padded_rows(m, rows)), *tiles), None


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
    m, rows = lhs.shape[0], padded_rows(lhs.shape[0], tiling[0])
    if rows != m:
        lhs = jnp.pad(lhs, ((0, rows - m), (0, 0)))
    return grouped_matmul_pallas(lhs, rhs, group_sizes, tiling)[:m]
