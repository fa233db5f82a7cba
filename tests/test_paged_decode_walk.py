"""The paged decode kernel's walk (kernels/flash_attention.py,
`_paged_decode_kernel`): a round copies the pages that hold a key its row
attends and no other, and a row's last round starts the next live row's
first. Held to `paged_decode_attention_reference` over pools in which
EVERY element no row attends is NaN: the blocks no live table entry maps,
the rows past a cursor inside its last page, the rows before a window's
first key inside its first page, and every dead table entry's block
(interpret mode, CPU). What the walk's host-side count says of a call is
held to the same rule."""

import importlib

import jax.numpy as jnp
import numpy as np
import pytest

# (the package exports a function of the module's name)
fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")

BS, W, ROUND = 16, 12, 4   # blocks of 16 rows, a table of 12, 4 pages a round
FULL = BS * W

# body variants: heads, KV heads, key head, value head, window, sink, lse
VARIANTS = {
    "plain": (4, 4, 128, 128, 0, False, False),
    "grouped": (4, 2, 128, 128, 0, False, False),
    # a window of 40 is 2 pages a round: key 60 of the row of 100 lies
    # inside page 3, key 64 of the row of 104 at page 4's first row
    "window_sink": (8, 4, 192, 128, 40, True, False),
    "k192_v128": (8, 4, 192, 128, 0, False, False),
    "lse": (4, 4, 128, 128, 0, False, True),
}

# rows' lengths (0: a dead row), and the rows that read ANOTHER row's table
# row. 1, block - 1, block, block + 1, a round, a round + 1, the table
PATTERNS = {
    "dead_first": ([0, 1, 15, 16, 17, 64, 65, 100, 104, FULL], {}),
    "dead_middle_and_two_in_a_row": ([FULL, 0, 0, 65, 17, 0, 1, 104], {}),
    "dead_last": ([64, 16, 100, 65, 0], {}),
    "all_dead": ([0, 0, 0], {}),
    "shared_table_rows": ([100, 37, FULL, 70, 0, 129], {1: 0, 3: 0, 5: 2}),
}


def poisoned_call(variant, pattern, seed=0):
    """(arguments of the kernel, of the reference, lengths): pools whose
    unattended elements are NaN for the kernel and 0 for the reference."""
    heads, kv, dk, dv, window, sink, _ = VARIANTS[variant]
    lengths, shared = PATTERNS[pattern]
    rs = np.random.RandomState(seed)
    n = len(lengths)
    nan_block = 0
    blocks = 1 + n * W
    pk = rs.randn(blocks, BS, kv * dk).astype(np.float32)
    pv = rs.randn(blocks, BS, kv * dv).astype(np.float32)
    table = np.full((n, W), nan_block, np.int32)
    attended = np.zeros((blocks, BS), bool)
    for r in range(n):  # (a shared table row is an earlier row's)
        table[r] = (table[shared[r]] if r in shared
                    else 1 + r * W + rs.permutation(W))
    for r, length in enumerate(lengths):
        keys = np.arange(max(length - window, 0) if window else 0, length)
        attended[table[r, keys // BS], keys % BS] = True
    # a dead entry (a page in which no row attends a key) points at the
    # NaN block
    table[~attended[table].any(axis=-1)] = nan_block
    pk[~attended] = pv[~attended] = np.nan
    q = rs.randn(n, 1, heads * dk).astype(np.float32)
    bias = (rs.randn(heads) * 2).astype(np.float32) if sink else None
    args = dict(num_heads=heads, num_kv_heads=kv, window=window,
                sink=None if bias is None else jnp.asarray(bias),
                scale=dk ** -0.5)
    lens = jnp.asarray(lengths, jnp.int32)
    kernel = (jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
              jnp.asarray(table), lens)
    oracle = (jnp.asarray(q), jnp.asarray(np.nan_to_num(pk)),
              jnp.asarray(np.nan_to_num(pv)), jnp.asarray(table),
              (lens - 1)[:, None])
    return kernel, oracle, args, np.asarray(lengths)


@pytest.mark.parametrize("pattern", list(PATTERNS))
@pytest.mark.parametrize("variant", list(VARIANTS))
def test_no_unattended_page_reaches_the_result(variant, pattern,
                                               monkeypatch):
    heads, kv, dk, dv, window, _, lse = VARIANTS[variant]
    # four pages a round whatever the variant's row: a table of three rounds
    monkeypatch.setattr(fa, "_PAGED_ROUND_BYTES",
                        ROUND * BS * kv * (dk + dv) * 4)
    assert fa._paged_round_pages(BS, kv * dk * 4, kv * dv * 4, W,
                                 window) == (2 if window else ROUND)
    kernel, oracle, args, lengths = poisoned_call(variant, pattern)
    got = fa.paged_flash_decode_attention(*kernel, return_lse=lse, **args)
    want = fa.paged_decode_attention_reference(*oracle, return_lse=lse,
                                               **args)
    if lse:
        (got, got_lse), (want, want_lse) = got, want
        got_lse, want_lse = np.asarray(got_lse), np.asarray(want_lse)[:, 0]
    got, want = np.asarray(got), np.asarray(want)
    live = lengths > 0
    assert got.shape == (len(lengths), 1, heads * dv)
    assert np.isfinite(got).all()
    assert np.abs(got[live] - want[live]).max(initial=0.0) < 2e-6
    assert (got[~live] == 0.0).all()  # a dead row ran no round
    if lse:
        assert np.abs(got_lse[live] - want_lse[live]).max(initial=0.0) < 2e-5
        assert (got_lse[~live] <= fa.NEG_INF).all()


@pytest.mark.parametrize("lengths, block, copied, handed", [
    # c13b-serve-chat's kind of call: pages of 16, every slot live
    ([140, 33, 16, 1], 16, 144 + 48 + 16 + 16, 3),
    # a dead row hands the start on without a round of its own to hide it
    ([0, 17, 0, 0, 5, 9], 16, 32 + 16 + 16, 1),
    ([0, 0], 16, 0, 0),
    ([], 16, 0, 0),
    ([300, 0, 129], 128, 384 + 256, 0),
])
def test_the_walks_count_of_a_call(lengths, block, copied, handed):
    assert fa.paged_walk_counts(lengths, block) == (copied, handed)
