"""The paged indexer kernel (kernels/sparse_selection.paged_index_scores)
against the XLA form of `index_scores_rows`, in interpret mode on the CPU,
at the two cells' geometries: blocks of 256, a page table 131 wide (a width
the round of 8 pages does not divide), indexer keys in 128-lane rows."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import sparse_selection as sel

BS, W, BLOCKS = 256, 131, 140
GEOMETRIES = {
    # keye-vl-2.0-30b-a3b: 16 heads of 64, zero-filled to the pool's 128 lanes
    "16x64in128": (16, 64),
    # deepseek-v3.2: 64 heads of 128
    "64x128": (64, 128),
}
LAST = W * BS - 1
CASES = {
    "last_row_of_a_page": [3 * BS - 1, 8 * BS - 1, 9 * BS - 1],
    "first_row_of_the_next": [3 * BS, 8 * BS, 16 * BS],
    "position_0": [0, 1, 0],
    "the_tables_last": [LAST, LAST - BS, 128 * BS],
    "a_dead_row": [-1, 700, -1, -1, 2100],
    "dead_rows_only": [-1, -1],
    "mixed_lengths": [5000, -1, 17, LAST, 2047, 2048, 2049],
}


def _operands(heads, dim, positions, shared=False, seed=0):
    rs = np.random.RandomState(seed)
    rows = len(positions)
    lanes = 128
    qi = np.zeros((rows, heads, lanes), np.float32)
    qi[..., :dim] = rs.randn(rows, heads, dim)
    pool = np.zeros((BLOCKS, BS, lanes), np.float32)
    pool[..., :dim] = rs.randn(BLOCKS, BS, dim)
    wt = rs.randn(rows, heads).astype(np.float32)
    table = rs.randint(0, BLOCKS, (rows, W)).astype(np.int32)
    if shared:
        table[:] = table[0]
    for r, p in enumerate(positions):
        if p < 0:  # a dead row's pages are never read: no such blocks
            table[r] = BLOCKS + 1000 + np.arange(W)
    return (jnp.asarray(qi), jnp.asarray(wt), jnp.asarray(pool),
            jnp.asarray(table), jnp.asarray(positions, jnp.int32))


def _check(args):
    got = np.asarray(sel.paged_index_scores(*args))
    qi, wt, pool, table, positions = args
    want = np.asarray(sel.index_scores_rows_reference(
        qi, wt, pool, jnp.minimum(table, BLOCKS - 1), positions))
    assert got.shape == want.shape == (len(positions), W * BS)
    assert got.dtype == np.float32
    # NEG exactly where the XLA form has it: past a row's position, and
    # everywhere for a dead row
    np.testing.assert_array_equal(got == sel.NEG, want == sel.NEG)
    seen = np.arange(W * BS)[None, :] <= np.asarray(positions)[:, None]
    np.testing.assert_array_equal(got == sel.NEG, ~seen)
    np.testing.assert_allclose(got[seen], want[seen], rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_kernel_equals_the_xla_form(geometry, case):
    _check(_operands(*GEOMETRIES[geometry], CASES[case]))


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_rows_that_share_a_table_row(geometry):
    """A chunk's rows laid out as single-query rows carry copies of one
    table row; so may two slots on one prefix."""
    _check(_operands(*GEOMETRIES[geometry], [4000, 4001, 4002, 300],
                     shared=True))


def test_bf16_operands_accumulate_in_float32():
    """The cells' precision: bf16 queries and keys, float32 scores,
    ReLU, weights and sum."""
    qi, wt, pool, table, positions = _operands(16, 64, [3000, -1, 600])
    args = (qi.astype(jnp.bfloat16), wt, pool.astype(jnp.bfloat16), table,
            positions)
    _check(args)
    assert sel.paged_index_scores(*args).dtype == jnp.float32


def test_off_a_tpu_the_serving_entry_point_is_the_xla_form():
    """`index_scores_rows` is what the two attention ops call: off a TPU
    it traces no Pallas call, as the paged decode op does not."""
    args = _operands(16, 64, [300, -1])
    text = str(jax.make_jaxpr(sel.index_scores_rows)(*args))
    assert "pallas_call" not in text
    np.testing.assert_array_equal(
        np.asarray(sel.index_scores_rows(*args[:3], jnp.minimum(
            args[3], BLOCKS - 1), args[4])),
        np.asarray(sel.index_scores_rows_reference(
            *args[:3], jnp.minimum(args[3], BLOCKS - 1), args[4])))


@pytest.mark.parametrize("width,block,lanes,itemsize,why", [
    (131, 256, 128, 2, None),            # keye2-serve-mediaqa
    (130, 256, 128, 2, None),            # dsv32-serve-sessions
    (40, 16, 128, 2, r"block_size 16 % 128"),
    (131, 256, 64, 2, r"64 lanes"),
    (1024, 256, 128, 2, r"bytes of VMEM"),
])
def test_gate_names_what_it_refuses(width, block, lanes, itemsize, why):
    gate = sel.paged_index_gate(width, block, lanes, itemsize)
    if why is None:
        assert gate is None
    else:
        assert re.search(why, gate)
