"""The selected rows' gather (kernels/sparse_selection.gather_selected: a
position's block picked out of the row's page-table row by comparison, then
XLA's row gather from the pool as it lies) against plain indexing, and the
two `attend_selected` functions over it against the form they had with the
page-table row looked up by `take_along_axis`, at the two cells' geometries:
4 KV groups of 8 heads of 128 over [k ; v] rows of 1,024 lanes, and 128
heads over latent rows of 640 lanes (576 as the model has them) whose first
512 are the values."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import sparse_grouped_attention as sga
from flexflow_tpu.kernels import sparse_latent_attention as sla
from flexflow_tpu.kernels import sparse_selection as sel

BS, W, BLOCKS = 128, 6, 40
SCALE = 0.09


def _grouped(q, pool, table, picked, valid):
    return sga.attend_selected(q, pool, table, picked, valid, kv_heads=4,
                               scale=SCALE)


def _latent(q, pool, table, picked, valid):
    return sla.attend_selected(q, pool, table, picked, valid,
                               latent_dim=512, scale=SCALE)


# name -> (the function, q's (heads, lanes), a pool row's lanes)
GEOMETRIES = {
    "4x8x128in1024": (_grouped, (32, 128), 1024),
    "128x640v512": (_latent, (128, 640), 640),
    "128x576v512": (_latent, (128, 576), 576),
}
# (selected rows K, the rows' valid counts): a prefix is valid, as
# `select_topk` leaves it (the selected positions first, ascending; a
# context shorter than the top-k is `arange` with its seen positions first)
CASES = {
    "all_valid": (256, [256, 256, 256]),
    "fewer_than_k_valid": (256, [255, 1, 130]),
    "a_dead_row": (256, [256, 0, 77]),
    "dead_rows_only": (128, [0, 0]),
    "k_of_no_whole_lane_tile": (200, [200, 99, 7]),
    "one_row": (128, [100]),
}


def _operands(geometry, K, counts, dtype=jnp.float32, seed=0):
    _, q_shape, lanes = GEOMETRIES[geometry]
    rs = np.random.RandomState(seed)
    rows = len(counts)
    q = rs.randn(rows, *q_shape)
    pool = rs.randn(BLOCKS, BS, lanes)
    pool[0] = 0.0  # the scratch block: what an invalid entry reads
    # a row's blocks lie scattered over the pool, in no order
    table = np.stack([rs.permutation(BLOCKS - 1)[:W] + 1
                      for _ in range(rows)]).astype(np.int32)
    picked = np.stack([rs.permutation(W * BS)[:K]
                       for _ in range(rows)]).astype(np.int32)
    valid = np.arange(K)[None, :] < np.asarray(counts)[:, None]
    return (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
            jnp.asarray(table), jnp.asarray(picked), jnp.asarray(valid))


def _lookup(table, picked, valid):
    """A position's block as the attention modules looked it up before."""
    block = jnp.take_along_axis(table, picked // BS, axis=1)
    return jnp.where(valid, block, 0)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_gathers_the_rows_plain_indexing_reads(geometry, case):
    """Every valid entry is its position's pool row through the page table;
    every invalid one a row of the scratch block."""
    K, counts = CASES[case]
    _, pool, table, picked, valid = _operands(geometry, K, counts)
    got = np.asarray(sel.gather_selected(pool, table, picked, valid))
    pool, table, picked, valid = map(np.asarray, (pool, table, picked, valid))
    block = np.where(valid, np.take_along_axis(table, picked // BS, 1), 0)
    assert got.shape == (len(counts), K, pool.shape[2])
    np.testing.assert_array_equal(got, pool[block, picked % BS])
    assert not got[~valid].any()


@pytest.mark.parametrize("case", ["a_dead_row", "fewer_than_k_valid",
                                  "k_of_no_whole_lane_tile"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_attend_selected_is_what_it_was_under_the_old_lookup(
        geometry, case, monkeypatch):
    """The attention over the gathered rows did not change: with the block
    looked up by `take_along_axis` again the two functions give the same
    numbers, a dead row's finite ones among them."""
    attend = GEOMETRIES[geometry][0]
    args = _operands(geometry, *CASES[case])
    got = np.asarray(attend(*args))
    for module in (sga, sla):  # each bound the name when it was imported
        monkeypatch.setattr(
            module, "gather_selected", lambda pool, table, picked, valid:
            pool[_lookup(table, picked, valid), picked % BS])
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, np.asarray(attend(*args)))


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_an_invalid_entry_is_never_attended(geometry):
    """What lies at an invalid entry's position does not reach the output:
    the entries past a row's valid prefix point at poisoned pool rows."""
    attend = GEOMETRIES[geometry][0]
    q, pool, table, picked, valid = _operands(geometry, 256, [256, 40, 0])
    clean = np.asarray(attend(q, pool, table, picked, valid))
    block = np.take_along_axis(np.asarray(table), np.asarray(picked) // BS, 1)
    offset, live = np.asarray(picked) % BS, np.asarray(valid)
    poisoned = np.asarray(pool).copy()
    poisoned[block[~live], offset[~live]] = 1e4
    # a poisoned row may be another entry's valid row: keep those clean
    poisoned[block[live], offset[live]] = np.asarray(pool)[
        block[live], offset[live]]
    got = np.asarray(attend(q, jnp.asarray(poisoned), table, picked, valid))
    np.testing.assert_array_equal(got, clean)


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_a_context_shorter_than_the_top_k(geometry):
    """`select_topk` over fewer candidates than k returns every position
    in order, valid where the row has seen it: exactly the seen ones are
    gathered, through the page table."""
    _, pool, table, _, _ = _operands(geometry, 128, [1, 1, 1])
    S = 3 * BS
    positions = np.array([S - 1, 200, -1])
    index = jnp.where(jnp.arange(S)[None, :] <= positions[:, None],
                      jnp.asarray(np.random.RandomState(3).randn(3, S),
                                  jnp.float32), sel.NEG)
    picked, valid = sel.select_topk(index, 2048)
    assert picked.shape == (3, S) and int(valid.sum()) == S + 201
    got = np.asarray(sel.gather_selected(pool, table, picked, valid))
    want = np.asarray(pool)[np.asarray(_lookup(table, picked, valid)),
                            np.asarray(picked) % BS]
    np.testing.assert_array_equal(got, want)
    assert not got[2].any()  # a row that has seen nothing reads zeros


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_a_context_longer_than_the_top_k(geometry):
    """`select_topk` over more candidates than k (the bisection and the
    compaction, no sort) hands `gather_selected` a valid prefix of exactly
    the k best positions, and a row with fewer candidates a shorter one:
    the rows gathered are those of numpy's stable argsort, and nothing
    behind a prefix is read."""
    _, pool, table, _, _ = _operands(geometry, 128, [1, 1, 1, 1])
    S, k = W * BS, 200
    positions = np.array([S - 1, 450, 99, -1])
    index = np.round(np.random.RandomState(5).randn(4, S), 1)  # ties
    index = np.where(np.arange(S)[None, :] <= positions[:, None], index,
                     sel.NEG).astype(np.float32)
    picked, valid = jax.jit(sel.select_topk, static_argnums=1)(
        jnp.asarray(index), k)
    assert picked.shape == (4, k)
    assert np.asarray(valid).sum(axis=1).tolist() == [k, k, 100, 0]
    got = np.asarray(sel.gather_selected(pool, table, picked, valid))
    for r, count in enumerate([k, k, 100, 0]):
        best = np.sort(np.argsort(-index[r], kind="stable")[:count])
        want = np.asarray(pool)[np.asarray(table)[r, best // BS], best % BS]
        np.testing.assert_array_equal(got[r, :count], want)
        assert not got[r, count:].any()


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_the_only_gather_is_the_rows(geometry):
    """What says the mechanism engaged: `attend_selected` traces ONE
    gather, the pool's rows. The page-table lookup is compares and a sum;
    as a second gather, of scalars, it cost XLA 10 ns an entry on the chip
    (PERF.md section 6, PR 44)."""
    attend = GEOMETRIES[geometry][0]
    args = _operands(geometry, 128, [128, 5], dtype=jnp.bfloat16)
    def gathers(jaxpr):  # through the nested jaxprs of `jit` equations too
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "gather":
                yield eqn.outvars[0].aval.shape
            for param in eqn.params.values():
                if hasattr(param, "jaxpr"):
                    yield from gathers(param.jaxpr)

    found = list(gathers(jax.make_jaxpr(attend)(*args).jaxpr))
    assert found == [(2, 128, args[1].shape[2])]
    assert attend(*args).dtype == jnp.bfloat16
