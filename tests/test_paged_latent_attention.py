"""The paged latent decode kernel (kernels/paged_latent_attention.
paged_latent_decode) against its jax.numpy form, in interpret mode on the
CPU, at `ms4-serve-longctx`'s geometry: 32 absorbed queries of 384 lanes (a
latent row of 256 + 64, stored 384 wide) of which the first 256 are the
value, blocks of 256 rows, and a page table whose width the round of 8
pages does not divide."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import paged_latent_attention as pla

BS, W, BLOCKS = 256, 19, 40
HEADS, LANES, LATENT, ROW = 32, 384, 256, 320
LAST = W * BS - 1
CASES = {
    "last_row_of_a_page": [3 * BS - 1, 8 * BS - 1, 9 * BS - 1],
    "first_row_of_the_next": [3 * BS, 8 * BS, 16 * BS],
    "position_0": [0, 1, 0],
    "the_tables_last": [LAST, LAST - BS, 16 * BS + 5],
    "a_dead_row": [-1, 700, -1, -1, 2100],
    "dead_rows_only": [-1, -1],
    "mixed_lengths": [4500, -1, 17, LAST, 2047, 2048, 2049],
}


def _operands(positions, shared=False, seed=0, dtype=jnp.float32):
    rs = np.random.RandomState(seed)
    rows = len(positions)
    q = np.zeros((rows, HEADS, LANES), np.float32)
    q[..., :ROW] = rs.randn(rows, HEADS, ROW)
    pool = np.zeros((BLOCKS, BS, LANES), np.float32)
    pool[..., :ROW] = rs.randn(BLOCKS, BS, ROW)
    table = rs.randint(1, BLOCKS, (rows, W)).astype(np.int32)
    if shared:
        table[:] = table[0]
    return (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
            jnp.asarray(table), jnp.asarray(positions, jnp.int32))


def _both(args, scale=0.2):
    got = pla.paged_latent_decode(*args, latent_dim=LATENT, scale=scale)
    want = pla.paged_latent_decode_reference(*args, latent_dim=LATENT,
                                             scale=scale)
    assert got.shape == want.shape == (args[0].shape[0], HEADS, LATENT)
    assert got.dtype == want.dtype == args[0].dtype
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_equals_the_xla_form(case):
    args = _operands(CASES[case])
    got, want = _both(args)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    dead = np.asarray(args[3]) < 0
    assert not np.any(got[dead])  # a dead row gives zeros


def test_the_xla_form_is_the_softmax_over_the_rows_past():
    """The oracle itself, against the sum written out for one row."""
    q, pool, table, positions = _operands([700])
    want = np.asarray(pla.paged_latent_decode_reference(
        q, pool, table, positions, latent_dim=LATENT, scale=0.2))
    keys = np.asarray(pool)[np.asarray(table)[0]].reshape(-1, LANES)[:701]
    scores = np.einsum("hc,sc->hs", np.asarray(q)[0], keys) * 0.2
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    np.testing.assert_allclose(want[0], p @ keys[:, :LATENT], rtol=1e-4,
                               atol=1e-5)


def test_only_live_pages_are_read():
    """A page past a row's length is never copied: poisoned with NaN it
    changes nothing, in the kernel (no DMA) and in the XLA form (masked as
    key and as value)."""
    q, pool, table, positions = _operands([3 * BS + 7, 600, -1])
    table = np.array(table)
    table[0, 4:], table[1, 3:], table[2, :] = 0, 0, 0  # block 0: dead pages
    clean = _both((q, pool, jnp.asarray(table), positions))
    poisoned = _both((q, pool.at[0].set(jnp.nan), jnp.asarray(table),
                      positions))
    for got, want in zip(poisoned, clean):
        assert np.all(np.isfinite(got))
        np.testing.assert_array_equal(got, want)


def test_rows_that_share_a_table_row():
    """Two slots on one prefix carry copies of one table row."""
    got, want = _both(_operands([4000, 4001, 4002, 300], shared=True))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_bf16_operands_accumulate_in_float32():
    """The cell's precision: bf16 queries and latent rows, float32 scores,
    softmax and accumulator, one rounding of the result."""
    got, want = _both(_operands([3000, -1, 600], dtype=jnp.bfloat16))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    exact = np.asarray(pla.paged_latent_decode_reference(
        *_operands([3000, -1, 600]), latent_dim=LATENT, scale=0.2))
    assert np.max(np.abs(got - exact)) < 0.03 * np.max(np.abs(exact))


def test_off_a_tpu_the_serving_entry_point_is_the_xla_form():
    """`attend_rows` is what the latent decode op calls: off a TPU it
    traces no Pallas call, as the other paged ops do not."""
    args = _operands([300, -1])
    fn = lambda *a: pla.attend_rows(*a, latent_dim=LATENT, scale=0.2)  # noqa: E731
    assert "pallas_call" not in str(jax.make_jaxpr(fn)(*args))
    np.testing.assert_array_equal(
        np.asarray(fn(*args)),
        np.asarray(pla.paged_latent_decode_reference(
            *args, latent_dim=LATENT, scale=0.2)))


@pytest.mark.parametrize("width,block,lanes,latent,heads,itemsize,why", [
    (260, 256, 384, 256, 32, 2, None),      # ms4-serve-longctx
    (130, 256, 640, 512, 128, 2, None),     # deepseek-v3.2's row, unselected
    (40, 4, 128, 32, 4, 4, r"block_size 4 % 8"),
    (260, 256, 320, 256, 32, 2, r"320 lanes"),
    (260, 256, 384, 32, 32, 2, r"32 of them the value"),
    (260, 256, 384, 256, 4, 2, r"4 heads"),
    (260, 256, 4096, 256, 32, 2, r"bytes of VMEM"),
])
def test_gate_names_what_it_refuses(width, block, lanes, latent, heads,
                                    itemsize, why):
    gate = pla.paged_latent_gate(width, block, lanes, latent, heads,
                                 itemsize)
    if why is None:
        assert gate is None
    else:
        assert re.search(why, gate)
