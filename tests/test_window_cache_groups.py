"""The serving cache's window group on the host, and the paged kernels a
window layer calls (PR 41). Pure host code first (serving/paged.py: no
jax): window blocks freed as a slot advances, reservation by group, a
prefix matched only where the window group holds the rows before it,
copy-on-write in both groups, eviction. Then the single-query paged decode
kernel in interpret mode and the tile loop a chunk takes, each against the
einsum oracle at key heads of 192 and value heads of 128, with and without
a window and a sink.
"""

import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.serving.paged import (
    SCRATCH_BLOCK, BlockManager, CopyPlan, WindowGroup,
)

fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")


def manager(blocks=40, window_blocks=20, bs=4, width=16, window=6, span=4):
    return BlockManager(blocks, bs, width, cross_time=True,
                        window_blocks=window_blocks, window=window,
                        window_span=span)


def prefill(m, slot, prompt, request, new=8, chunk=4):
    """Admission and the prompt's chunks, as the engine drives them; ->
    (tokens skipped, every step's copies)."""
    assert m.reserve(request, len(prompt), new, prompt=prompt)
    m.bind_reservation(request, slot)
    skip = m.admit(slot, prompt)
    copies = []
    for start in range(skip, len(prompt), chunk):
        copies += m.ensure_writable(
            slot, range(start, min(start + chunk, len(prompt))))
    m.register_prompt(slot, prompt)
    m.check_invariants()
    return skip, copies


def held(table):
    return [lb for lb, blk in enumerate(table) if blk != SCRATCH_BLOCK]


# ------------------------------------------------------------- the manager

def test_a_slot_holds_the_window_and_a_steps_rows_and_no_more():
    m = manager()
    # rows a step's first row reads start at position - 5: block
    # (position - 5) // 4
    assert [m.window.first_block(p) for p in (0, 5, 6, 9, 23)] == [
        0, 0, 0, 1, 4]
    # the window before a step of 4 rows and the step: 9 rows = 3 blocks,
    # and two of slack
    assert m.window.slot_blocks == 5
    prompt = list(range(23))
    assert m.reserve(1, 23, 8, prompt=prompt)
    m.bind_reservation(1, 0)
    assert m.admit(0, prompt) == 0
    seen = []
    for start in range(0, 23, 4):
        m.ensure_writable(0, range(start, min(start + 4, 23)))
        seen.append(held(m.window_table(0)))
        m.window.check_invariants()
    # chunk [12, 16) reads from row 7: block 1 on; block 0 is gone
    assert seen == [[0], [0, 1], [0, 1, 2], [1, 2, 3], [2, 3, 4], [3, 4, 5]]
    assert m.stats.window_blocks_freed == 3
    # the global group holds every block still
    assert held(m.table(0)) == [0, 1, 2, 3, 4, 5]
    # decoding on: one row a step, the window slides a block at a time
    for pos in range(23, 34):
        m.ensure_writable(0, range(pos, pos + 1))
        assert held(m.window_table(0)) == list(
            range(m.window.first_block(pos), pos // 4 + 1))
        assert len(held(m.window_table(0))) <= 3
    assert m.stats.window_blocks_in_use_peak == 3
    m.release(0)
    assert m.window.blocks_in_use == 0 == m.blocks_in_use
    m.check_invariants()


def test_reservation_is_by_group():
    """The global group reserves prompt + new; the window group a slot's
    share, whatever the prompt: with 11 window blocks two slots of 5 fit
    and a third does not, though the global pool has room."""
    m = manager(blocks=200, window_blocks=11, width=40)
    assert m.reserve(1, 100, 20) and m.reserve(2, 4, 1)
    assert m.reserved_total == 30 + 2
    assert sum(m.window._reserved.values()) == 10
    assert not m.reserve(3, 4, 1)
    assert ("req", 3) not in m._reserved
    m.bind_reservation(1, 0)
    m.admit(0, list(range(100)))
    m.release(0)
    assert m.reserve(3, 4, 1)
    with pytest.raises(ValueError, match="window blocks"):
        WindowGroup(1, 4, 6, 4, lambda: None)


def test_a_prefix_is_matched_only_where_the_window_group_holds_its_rows():
    m = manager()
    prompt = list(range(23))
    prefill(m, 0, prompt, 1)
    # published: every block in the global group, the blocks of the last
    # chunk's window (row 15 on) in the window group
    assert len(m.cache.pinned) == 6 and len(m._wpins) == 3
    assert sorted(m._wpins) == sorted(m.cache.match(prompt, peek=True)[1][3:])
    m.release(0)
    follow = prompt + [99, 98, 97]
    # the whole history: its last 5 rows lie in window blocks 4 and 5
    assert m.match_prefix(follow) == 23
    # a prompt that parts at row 12 finds 12 rows in the global group and
    # none of the rows before them in the window group: nothing is usable
    assert m.cache.match(prompt[:12] + [5], peek=True)[0] == 12
    assert m.match_prefix(prompt[:12] + [5]) == 0
    # one that parts at row 18 (inside block 4): rows 13..17 lie in blocks
    # 3 and 4, both held
    assert m.match_prefix(prompt[:18] + [5]) == 18
    # the whole prompt again: all but its last token, whose row is computed
    assert m.match_prefix(prompt) == 23 and m.admit(1, prompt) == 22
    assert held(m.window_table(1)) == [4, 5]
    assert held(m.table(1)) == [0, 1, 2, 3, 4, 5]
    m.check_invariants()


def test_a_match_falls_back_to_a_block_boundary_the_window_group_holds():
    m = manager()
    prompt = list(range(24))
    prefill(m, 0, prompt, 1)
    m.release(0)
    # the tail's window block is given up: the extent is usable up to the
    # boundary before it, where blocks 3 and 4 hold rows 15..19
    tail = m.cache.match(prompt, peek=True)[1][-1]
    m._unpin_window(tail)
    assert m.match_prefix(prompt + [7]) == 20
    assert m.admit(1, prompt + [7]) == 20
    assert held(m.table(1)) == [0, 1, 2, 3, 4]
    assert held(m.window_table(1)) == [3, 4]


def test_copy_on_write_copies_the_shared_tail_block_in_both_groups():
    m = manager()
    prompt = list(range(23))
    prefill(m, 0, prompt, 1)
    # the slot's own next row lies in its published tail block
    copies = m.ensure_writable(0, range(23, 24))
    assert sorted(c.group for c in copies) == [0, 1]
    m.release(0)
    skip, copies = prefill(m, 1, prompt + [99, 98, 97], 2)
    assert skip == 23
    by_group = {c.group: c for c in copies}
    assert set(by_group) == {0, 1}
    history = m.cache.match(prompt, peek=True)[1]
    assert by_group[0].src == history[-1]
    assert by_group[1].src == m._wpins[history[-1]]
    assert by_group[0].dst == m.table(1)[5]
    assert by_group[1].dst == m.window_table(1)[5]
    assert (m.stats.cow_copies, m.stats.window_cow_copies) == (2, 2)
    assert CopyPlan(1, 2).group == 0


def test_a_slot_in_need_takes_the_window_block_of_an_unmatched_leaf_first():
    """Pressure on the window group: the blocks of finished requests'
    questions go first (nothing was ever matched through them), leaves
    before their parents and out of the cache whole; a history that is
    matched again and again keeps its window."""
    m = manager(blocks=80, window_blocks=16, width=20)
    history = list(range(100, 122))
    prefill(m, 0, history, 0, new=1)
    m.release(0)
    for i in range(1, 12):
        prompt = history + [i, i, i, i, i, i]
        skip, _ = prefill(m, 0, prompt, i, new=2)
        assert skip == 22, i
        m.release(0)
    assert m.stats.window_pins_dropped > 0
    assert m.stats.radix_evictions >= m.stats.window_pins_dropped
    assert m.match_prefix(history + [50]) == 22
    m.check_invariants()


def test_eviction_takes_the_window_block_with_the_node():
    m = manager(blocks=12, window_blocks=20, width=10)
    first = list(range(20))
    prefill(m, 0, first, 1, new=1)
    m.release(0)
    nodes = set(m._wpins)
    assert nodes and m.window.blocks_held == len(nodes) == 3
    # a second prompt needs the whole global pool: the first is evicted,
    # leaf by leaf, and its window blocks go with its nodes
    second = list(range(50, 90))
    prefill(m, 0, second, 2, new=1)
    assert set(m._wpins) <= set(m.cache.match(second, peek=True)[1])
    assert m.window.blocks_held == len(m._wpins) == 3
    assert m.match_prefix(first) == 0
    assert m.stats.radix_evictions >= 4
    m.check_invariants()


def test_a_manager_without_a_window_group_is_what_it_was():
    m = BlockManager(16, 4, 8, cross_time=True)
    assert m.window is None
    prompt = list(range(11))
    assert m.reserve(1, 11, 4, prompt=prompt)
    m.bind_reservation(1, 0)
    assert m.admit(0, prompt) == 0
    assert m.ensure_writable(0, range(0, 11)) == []
    m.register_prompt(0, prompt)
    m.release(0)
    assert m.match_prefix(prompt[:6] + [0]) == 6
    m.check_invariants()


# ------------------------------------------------------------- the kernels

RNG = np.random.default_rng(0)


def pools(kv, dk, dv, blocks, bs, dtype=jnp.float32):
    return (jnp.asarray(RNG.normal(size=(blocks, bs, kv * dk)), dtype),
            jnp.asarray(RNG.normal(size=(blocks, bs, kv * dv)), dtype))


@pytest.mark.parametrize("block_size, k_row, v_row, width, window, pages", [
    (16, 4096, 4096, 40, 0, 8),        # c13b-serve-chat: 16 heads of 128
    (256, 2048, 2048, 131, 0, 1),      # cmdap-serve-agentmix, global
    (256, 2048, 2048, 131, 4096, 1),   # and window: 1 MiB a block
    (256, 2048, 2048, 66, 0, 1),       # solar2-serve-reason's softmax layer
    (128, 1536, 1024, 262, 0, 4),      # mimo2f-serve-longdoc, global
    (128, 3072, 2048, 262, 128, 1),    # and window: the window's one page,
    (128, 3072, 2048, 262, 0, 2),      # where its bytes alone would ask two
    (128, 2048, 2048, 262, 0, 2),      # cmdap's rows on blocks of 128
    (16, 48 * 4, 32 * 4, 12, 0, 12),   # never more than the table is wide
    (128, 1 << 15, 1 << 15, 8, 0, 1),  # a page past the target: one
])
def test_a_round_is_sized_by_its_bytes(block_size, k_row, v_row, width,
                                       window, pages):
    """About `_PAGED_ROUND_BYTES` of K + V a round, whatever a row's width
    (rows are bytes here: lanes x itemsize)."""
    assert fa._paged_round_pages(block_size, k_row, v_row, width,
                                 window) == pages


def test_two_rounds_fit_the_kernels_vmem(monkeypatch):
    """The rule never answers more pages than two double-buffered rounds
    of `_PAGED_ROUND_VMEM` hold, and the gate reckons from the same answer:
    it refuses only a page that does not fit alone."""
    mimo = (128, 1536, 1024, 262)
    assert fa._paged_round_pages(*mimo) == 4
    monkeypatch.setattr(fa, "_PAGED_ROUND_VMEM", 2 << 20)
    assert fa._paged_round_pages(*mimo) == 3     # 2 x 3 x 320 KiB
    assert fa.paged_decode_gate(33536, 128, 768, 4, 2, False, 512) is None
    monkeypatch.setattr(fa, "_PAGED_ROUND_VMEM", 512 << 10)
    assert fa._paged_round_pages(*mimo) == 1
    gate = fa.paged_decode_gate(33536, 128, 768, 4, 2, False, 512)
    assert "two rounds of 128 rows" in gate and "VMEM" in gate


# a table's width and its rows' lengths, by block size. Blocks of 16: an
# empty slot, one key, inside the first page, several pages, full. Blocks
# of 128 under rounds of 2 and of 4 pages, a table of 9: lengths that end
# in each page of rounds [4, 8) and [4, 6), [6, 8), and a full table, whose
# last round holds page 8 and re-reads it for the pages past the table
GEOMETRY = {16: (12, [0, 1, 37, 150, 192]),
            128: (9, [0, 1, 515, 740, 832, 1024, 1152])}


@pytest.mark.parametrize("heads, kv, dk, dv, window, sink, bs, pages", [
    (8, 4, 192, 128, 0, False, 16, 12),   # a global layer's widths, KV heads
    (8, 4, 192, 128, 128, True, 16, 8),   # cut; a window layer's
    (8, 4, 192, 128, 128, False, 16, 8),
    (8, 4, 192, 128, 0, True, 16, 12),
    (8, 2, 24, 16, 20, True, 16, 1),      # the test model's
    (4, 4, 16, 16, 20, True, 16, 1),      # one head size, no groups
    (4, 2, 128, 128, 0, False, 16, 12),   # what the kernel was: grouped
    (4, 4, 128, 128, 0, False, 16, 12),   # and not
    # rounds of several blocks of 128: a row of 5,120 B (MiMo's window
    # layer's, in float32) is 2 pages a round, one of 2,560 B 4
    (8, 4, 192, 128, 0, False, 128, 2),
    (8, 4, 192, 128, 0, True, 128, 2),
    (8, 2, 192, 128, 0, False, 128, 4),
    (4, 2, 128, 128, 0, False, 128, 4),
    # a window whose first key lies in a round's second page: key 440 of
    # the row of 740 under rounds of 256, key 232 of the row of 832 under
    # rounds of 512; and one that spans fewer pages than the bytes ask
    (8, 4, 192, 128, 300, True, 128, 2),
    (8, 2, 192, 128, 600, True, 128, 4),
    (8, 2, 192, 128, 300, False, 128, 2),
])
def test_the_paged_decode_kernel_is_its_einsum_oracle(heads, kv, dk, dv,
                                                      window, sink, bs,
                                                      pages):
    W, lengths = GEOMETRY[bs]
    slots = len(lengths)
    pk, pv = pools(kv, dk, dv, slots * W + 1, bs)
    assert fa._paged_round_pages(bs, kv * dk * 4, kv * dv * 4, W,
                                 window) == pages
    q = jnp.asarray(RNG.normal(size=(slots, 1, heads * dk)), jnp.float32)
    table = jnp.asarray(
        1 + RNG.permutation(slots * W).reshape(slots, W), jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    bias = (jnp.asarray(RNG.normal(size=(heads,)) * 2, jnp.float32)
            if sink else None)
    got = fa.paged_flash_decode_attention(
        q, pk, pv, table, lengths, num_heads=heads, num_kv_heads=kv,
        window=window, sink=bias)
    want = fa.paged_decode_attention_reference(
        q, pk, pv, table, (lengths - 1)[:, None], num_heads=heads,
        num_kv_heads=kv, window=window, sink=bias, scale=dk ** -0.5)
    assert got.shape == (slots, 1, heads * dv)
    assert float(jnp.max(jnp.abs(got[1:] - want[1:]))) < 2e-6
    assert float(jnp.max(jnp.abs(got[0]))) == 0.0


def test_a_windowed_row_reads_nothing_behind_its_window():
    """Blocks behind the window may be anything (the manager maps the
    scratch block there): NaNs in them change nothing."""
    heads, kv, dk, dv, bs, W = 8, 4, 192, 128, 16, 12
    pk, pv = pools(kv, dk, dv, W + 2, bs)
    q = jnp.asarray(RNG.normal(size=(1, 1, heads * dk)), jnp.float32)
    table = jnp.arange(1, W + 1, dtype=jnp.int32)[None]
    lengths = jnp.asarray([150], jnp.int32)
    kw = dict(num_heads=heads, num_kv_heads=kv, window=32,
              sink=jnp.zeros((heads,), jnp.float32))
    want = fa.paged_flash_decode_attention(q, pk, pv, table, lengths, **kw)
    # rows [118, 150) are read: blocks 7, 8, 9; blocks 0..6 are behind
    nan = W + 1
    pk, pv = pk.at[nan].set(jnp.nan), pv.at[nan].set(jnp.nan)
    behind = table.at[0, :7].set(nan)
    got = fa.paged_flash_decode_attention(q, pk, pv, behind, lengths, **kw)
    assert bool(jnp.all(got == want))


@pytest.mark.parametrize("window, sink, start, tile_rows", [
    (0, False, 70, 64), (20, True, 70, 64), (20, True, 0, 64),
    (0, True, 70, 2048), (128, True, 150, 64)])
def test_the_chunk_tile_loop_is_its_einsum_oracle(window, sink, start,
                                                  tile_rows):
    heads, kv, dk, dv, bs, W, b = 8, 2, 24, 16, 16, 16, 24
    pk, pv = pools(kv, dk, dv, W + 1, bs)
    q = jnp.asarray(RNG.normal(size=(b, 1, heads * dk)), jnp.float32)
    row = jnp.asarray(1 + RNG.permutation(W), jnp.int32)
    # a chunk of 20 rows and the bucket's 4 dead rows
    pos = jnp.asarray(np.r_[start + np.arange(b - 4), [-1] * 4], jnp.int32)
    bias = (jnp.asarray(RNG.normal(size=(heads,)) * 2, jnp.float32)
            if sink else None)
    got = fa.paged_chunk_attention_tiled(
        q, pk, pv, row, pos, num_heads=heads, num_kv_heads=kv,
        scale=dk ** -0.5, window=window, sink=bias, tile_rows=tile_rows)
    want = fa.paged_decode_attention_reference(
        q, pk, pv, jnp.broadcast_to(row, (b, W)), pos[:, None],
        num_heads=heads, num_kv_heads=kv, window=window, sink=bias,
        scale=dk ** -0.5)
    assert float(jnp.max(jnp.abs(got[:b - 4] - want[:b - 4]))) < 2e-6
    assert float(jnp.max(jnp.abs(got[b - 4:]))) == 0.0


def test_the_gates_admit_what_the_kernels_can_tile():
    # on the chip: rows of whole 128-lane tiles, value heads of 128
    assert fa.paged_decode_gate(33536, 128, 768, 4, 2, False, 512) is None
    assert fa.paged_decode_gate(33536, 128, 1536, 8, 2, False, 1024) is None
    # key heads of 192 in a row that is no whole tiles; value heads of 64
    assert "128-lane" in fa.paged_decode_gate(4096, 128, 192 * 3, 3, 2,
                                              False, 128 * 3)
    assert "head_dim 64" in fa.paged_decode_gate(4096, 128, 768, 4, 2,
                                                 False, 256)
    # as before: heads of 128 pass, heads of 64 do not
    assert fa.paged_decode_gate(4096, 16, 2048, 16, 2, False) is None
    assert "head_dim 64" in fa.paged_decode_gate(4096, 16, 1024, 16, 2, False)
    # a chunk's rows of a layer with a sink and key and value heads of two
    # sizes take the tile loop, by name; its window alone would not
    from flexflow_tpu.ops import inc_attention as inc
    from flexflow_tpu.ops.attention import AttentionFrontEnd

    front = AttentionFrontEnd(4096, 64, False, 1e4, num_kv_heads=8,
                              head_size=192, v_head_size=128, rope_dim=64,
                              window=128, sink=True)
    p = inc.PagedIncMultiHeadAttentionParams(front, 33536, 128, 512,
                                             impl="flash", chunk_from=32)
    assert "192 / 128, sink True" in inc._chunk_gate(p, 256, 2)
    assert inc.paged_chunk_query_tile(p, None, 2, 256) == 256
    windowed = inc.PagedIncMultiHeadAttentionParams(
        AttentionFrontEnd(4096, 64, False, 1e4, num_kv_heads=8,
                          head_size=128, window=128),
        33536, 128, 512, impl="flash", chunk_from=32)
    assert inc._chunk_gate(windowed, 256, 2) is None
    assert p.cache_row_widths == {"pool_k": 1536, "pool_v": 1024}


def test_the_kernels_serve_the_engine_in_interpret_mode():
    """serve(impl="flash") over a cache of 128 rows in blocks of 16: the
    interpreter runs the paged decode kernel (grouped, key heads of 24
    through `spread`, the window walk, the sink) for the slots' rows and
    the tile loop for a chunk's, which rides as rows; the tokens are the
    reference's greedy continuation."""
    from test_mimo_v2_flash import build
    from test_mimo_v2_flash_serving import is_greedy

    big = build(seq=128, batch=1)
    # serve(): the model is this test's alone
    eng = big.serve(slots=2, max_seq_len=128, prefill_chunk=8,
                    kv_block_size=16, impl="flash")
    assert eng._chunk_rows
    prompt = np.random.default_rng(6).integers(0, 97, 27).tolist()
    out = eng.generate([prompt], max_new_tokens=5)
    assert len(out[0]) == 5 and is_greedy(big, prompt, out[0])
    st = eng.stats()
    assert st["row_steps"] == st["prefill_calls"] > 0
    assert st["window_blocks_freed"] > 0
