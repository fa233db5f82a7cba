"""The multi-query paged attention kernel of a prefill chunk
(kernels/flash_attention.paged_flash_chunk_attention), interpret mode on
the CPU: the kernel against the gather-and-einsum oracle and against the
single-query kernel on the same rows, the op's choice between the two
(ops/inc_attention._paged_mha_forward, `chunk_from`), and the engine's
count of the steps the kernel took (`stats()["chunk_kernel_steps"]`)."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.fftype import DataType, OperatorType as OT
from flexflow_tpu.ops import inc_attention as inc
from flexflow_tpu.ops.attention import AttentionFrontEnd
from flexflow_tpu.ops.base import OpContext, get_op_def
from small_lms import ROWS, build_rows_lm, engine

fa = importlib.import_module("flexflow_tpu.kernels.flash_attention")

# as tests/test_serving.py's paged parity: the kernel normalises after the
# p·V contraction, the oracle before it
TOL = {"float32": 2e-5, "bfloat16": 2e-2}


def _chunk_case(b, n, start, bs, W, H, KV, hd, dtype, seed=0, window=0):
    """A chunk of `n` tokens at `start` in a bucket of `b` rows over one
    slot's scrambled page-table row: every pool row past the chunk's end,
    mapped or not, holds NaN, the scratch block too; under a `window` so
    does every block wholly under the first row's window (a window
    group's table points those at its scratch block)."""
    rs = np.random.RandomState(seed)
    nb = W + 4
    pool_k = rs.randn(nb, bs, KV * hd).astype(np.float32)
    pool_v = rs.randn(nb, bs, KV * hd).astype(np.float32)
    table = rs.permutation(np.arange(1, nb))[:W].astype(np.int32)
    end = start + n
    for j, blk in enumerate(table):
        dead = max(0, min(bs, (j + 1) * bs - end))
        if dead:
            pool_k[blk, bs - dead:] = pool_v[blk, bs - dead:] = np.nan
        if window and (j + 1) * bs <= start + 1 - window:
            pool_k[blk] = pool_v[blk] = np.nan
    unmapped = np.setdiff1d(np.arange(nb), table)
    pool_k[unmapped] = pool_v[unmapped] = np.nan
    lengths = np.where(np.arange(b) < n, start + 1 + np.arange(b),
                       0).astype(np.int32)
    dt = jnp.dtype(dtype)
    q = jnp.asarray(rs.randn(b, 1, H * hd), dt)
    return (q, jnp.asarray(pool_k, dt), jnp.asarray(pool_v, dt),
            jnp.asarray(table), jnp.asarray(lengths))


def _three_ways(q, pool_k, pool_v, table, lengths, H, KV, window=0):
    """(chunk kernel, single-query kernel, oracle) on the same rows."""
    b, W = q.shape[0], table.shape[0]
    kw = dict(num_heads=H, num_kv_heads=KV, window=window)
    tables = jnp.broadcast_to(table, (b, W))
    out = fa.paged_flash_chunk_attention(q, pool_k, pool_v, table, lengths,
                                         **kw)
    single = fa.paged_flash_decode_attention(q, pool_k, pool_v, tables,
                                             lengths, **kw)
    # the oracle's 0·NaN is NaN: it reads the pool with the NaN (all in
    # masked rows) cleaned out
    ref = fa.paged_decode_attention_reference(
        q, jnp.nan_to_num(pool_k), jnp.nan_to_num(pool_v), tables,
        (lengths - 1)[:, None], **kw)
    return tuple(np.asarray(x, np.float32) for x in (out, single, ref))


def _tile_loop(q, pool_k, pool_v, table, lengths, H, KV, window=0):
    """The tile loop in XLA on the same rows (its 0·NaN is NaN too)."""
    return np.asarray(fa.paged_chunk_attention_tiled(
        q, jnp.nan_to_num(pool_k), jnp.nan_to_num(pool_v), table,
        lengths - 1, num_heads=H, num_kv_heads=KV, window=window,
        scale=(q.shape[-1] // H) ** -0.5), np.float32)


def _calls(fn, *args) -> dict:
    """{kernel name: pallas_calls} of fn's jaxpr (interpret mode leaves no
    custom call in the compiled text to count)."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                found[name] = found.get(name, 0) + 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.fixture
def rounds_of_64_rows(monkeypatch):
    """The cases below are laid out around rounds of 64 rows (a chunk that
    crosses a round, a window whose walk leaves the first rounds out). At
    these tests' narrow rows the rule of bytes answers the whole table, one
    round (the tests that do not ask for this fixture run so; at
    c13b-serve-chat's rows it gives 128, 8 pages of 16, which
    tests/test_chip_compile.py compiles). Four pages of 16 and not eight:
    the interpreter's lowering of a kernel is paid by the page, a DMA each
    for keys and values, and these cases are most of this file's time."""
    monkeypatch.setattr(fa, "_paged_round_pages",
                        lambda block_size, *a, **kw: max(1, 64 // block_size))


# a chunk of 32 at 200 over 16 blocks of 16 (rounds of 64 rows), under
# each kind of window: shorter than `start` (the walk leaves out rounds 0
# and 1), longer than the whole context, and beginning inside the first
# round
_WINDOWS = {"short": (200, 40), "long": (200, 1000), "inside": (100, 60)}
_CASES = [
    (16, 16, 0, 16, 16, 2, 2, 128, 0),     # a prompt's first chunk
    (32, 32, 37, 16, 16, 2, 2, 128, 0),    # start no multiple of a block
    (32, 32, 120, 16, 16, 2, 2, 128, 0),   # the chunk crosses a round
    (32, 20, 100, 16, 16, 2, 2, 128, 0),   # n < b: dead padding rows
    (16, 9, 200, 8, 32, 4, 4, 64, 0),      # blocks of 8, 8 pages a round
    (16, 16, 250, 256, 2, 2, 2, 128, 0),   # blocks of 256: a page a round
    (16, 11, 130, 16, 16, 16, 2, 64, 0),   # group 8: 16 query heads, 2 KV
    (32, 32, 300, 256, 2, 8, 1, 128, 0),   # group 8 over blocks of 256
    (256, 200, 100, 16, 24, 2, 1, 64, 0),  # two query tiles, the second ragged
    (32, 20, 100, 16, 16, 8, 2, 64, 0),    # group 4
    (32, 20, 100, 16, 16, 16, 1, 64, 0),   # group 16: a pass of 512 rows
    (48, 40, 60, 16, 16, 32, 2, 32, 0),    # group 16, two passes a KV head
]
_IDS = ["start0", "start37", "crosses-a-round", "dead-rows", "bs8", "bs256",
        "group8", "group8-bs256", "two-query-tiles", "group4", "group16",
        "group16-two-passes"]
for _kind, (_start, _window) in _WINDOWS.items():
    for _group in (1, 16):
        _CASES.append((32, 30, _start, 16, 16, 16, 16 // _group, 64, _window))
        _IDS.append(f"window-{_kind}-group{_group}")
_CASES += [(32, 30, 200, 16, 16, 8, 2, 64, 40),     # group 4, rounds left
           (256, 200, 140, 16, 32, 8, 1, 64, 100)]  # two tiles, two firsts
_IDS += ["window-short-group4", "window-two-query-tiles-group8"]


@pytest.mark.usefixtures("rounds_of_64_rows")
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("b,n,start,bs,W,H,KV,hd,window", _CASES, ids=_IDS)
def test_chunk_kernel_matches_the_oracle_and_the_single_query_kernel(
        b, n, start, bs, W, H, KV, hd, window, dtype):
    """Chunk row i attends `start + i + 1` keys (under a window its last
    `window`) through ONE walk of the table row: equal to the oracle and
    to the tile loop in XLA on the live rows, and to the single-query
    kernel on every row, the dead ones (0) included; rows of NaN past the
    chunk's end and under its first row's window never reach an output."""
    case = _chunk_case(b, n, start, bs, W, H, KV, hd, dtype, window=window)
    assert fa.paged_chunk_gate(b, W * bs, bs, H * hd, KV * hd, H,
                               case[1].dtype.itemsize, True) is None
    if window:
        assert np.isnan(np.asarray(case[1], np.float32)[
            np.asarray(case[3])[:max(0, start + 1 - window) // bs]]).all()
    out, single, ref = _three_ways(*case, H, KV, window)
    tiled = _tile_loop(*case, H, KV, window)
    assert np.isfinite(out).all()
    live = np.asarray(case[-1]) > 0
    assert live.sum() == n
    tol = TOL[dtype]
    np.testing.assert_allclose(out[live], ref[live], rtol=tol, atol=tol)
    np.testing.assert_allclose(out[live], tiled[live], rtol=tol, atol=tol)
    np.testing.assert_allclose(out, single, rtol=tol, atol=tol)
    assert not out[~live].any()


@pytest.mark.parametrize("b,n,start,bs,W,H,KV,hd,window,chunk,single", [
    (32, 32, 120, 16, 16, 2, 2, 128, 0, 16, 16),  # 4,096 B a row: 16 pages
    (32, 30, 200, 16, 16, 16, 1, 64, 40, 16, 2),  # a window of 2 pages
    (32, 20, 100, 16, 24, 8, 2, 64, 0, 24, 24),   # narrow rows: the table
])
def test_chunk_kernel_under_rounds_of_the_rules_own_size(
        b, n, start, bs, W, H, KV, hd, window, chunk, single):
    """The same three ways where a round is what the rule of bytes gives
    each kernel (`chunk`, `single`: its pages): the chunk kernel the rows'
    whole bytes, the single-query kernel no more than its window's pages."""
    case = _chunk_case(b, n, start, bs, W, H, KV, hd, "float32",
                       window=window)
    assert fa._paged_chunk_round_pages(bs, KV * hd, 4, W) == chunk
    assert fa._paged_round_pages(bs, KV * hd * 4, KV * hd * 4, W,
                                 window) == single
    out, one_by_one, ref = _three_ways(*case, H, KV, window)
    live = np.asarray(case[-1]) > 0
    np.testing.assert_allclose(out[live], ref[live], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out, one_by_one, rtol=2e-5, atol=2e-5)
    assert not out[~live].any()


@pytest.mark.usefixtures("rounds_of_64_rows")
def test_a_window_walk_starts_at_each_query_tiles_own_round():
    """The lowest key a query tile attends is its own: of two tiles of a
    chunk of 200 at 140 under a window of 100, over rounds of 64 rows,
    the first starts at round 0 (key 41) and the second at round 2 (key
    169), and the kernel is named for its walk."""
    case = _chunk_case(256, 200, 140, 16, 32, 8, 1, 64, "float32",
                       window=100)
    lengths = np.asarray(case[-1]).reshape(2, 128)
    lo = [int(np.maximum(t[t > 0].min() - 100, 0)) for t in lengths]
    assert [x // 64 for x in lo] == [0, 2]
    assert _calls(lambda *a: fa.paged_flash_chunk_attention(
        *a, num_heads=8, num_kv_heads=1, window=100), *case) == {
            "flash_attention_paged_chunk_window_grouped": 1}
    assert _calls(lambda *a: fa.paged_flash_chunk_attention(
        *a, num_heads=8, num_kv_heads=8, window=100),
        *_chunk_case(32, 30, 200, 16, 16, 8, 8, 64, "float32",
                     window=100)) == {"flash_attention_paged_chunk_window": 1}


def test_lengths_need_not_be_consecutive():
    """The mask is key_pos < length[i] and nothing else: rows in any
    order, repeated, and dead rows between live ones."""
    b, bs, W, H, hd = 16, 16, 16, 2, 128
    q, pool_k, pool_v, table, _ = _chunk_case(b, b, 200, bs, W, H, H, hd,
                                              "float32")
    lengths = jnp.asarray([216, 1, 0, 130, 129, 128, 127, 0, 216, 5, 17, 16,
                           15, 0, 201, 64], jnp.int32)
    out, single, ref = _three_ways(q, pool_k, pool_v, table, lengths, H, H)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(out[live], ref[live], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out, single, rtol=2e-5, atol=2e-5)


@pytest.mark.usefixtures("rounds_of_64_rows")
def test_a_split_head_tile_reads_its_own_lanes(monkeypatch):
    """Where all the KV heads' buffers do not fit, a grid step takes some
    of them and the query heads that read them, from its own lanes of the
    pool's rows (Solar-Open2's widths on the chip; a small budget here)."""
    b, bs, W, H, KV, hd = 32, 16, 16, 8, 4, 128
    case = _chunk_case(b, 20, 120, bs, W, H, KV, hd, "float32")
    whole = fa._paged_chunk_tile(b, H * hd, KV * hd, H, 128, 4, True)
    assert whole == KV
    monkeypatch.setattr(fa, "_PAGED_CHUNK_VMEM", 700_000)
    assert fa._paged_chunk_tile(b, H * hd, KV * hd, H, 128, 4, True) == 1
    calls = _calls(lambda *a: fa.paged_flash_chunk_attention(
        *a, num_heads=H, num_kv_heads=KV), *case)
    assert calls == {"flash_attention_paged_chunk_grouped": 1}
    out, single, ref = _three_ways(*case, H, KV)
    live = np.asarray(case[-1]) > 0
    np.testing.assert_allclose(out[live], ref[live], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out, single, rtol=2e-5, atol=2e-5)


def test_a_shape_the_tile_cannot_take_runs_the_single_query_kernel(
        monkeypatch):
    """Not the reference: a row through the reference gathers a whole
    logical cache."""
    b, bs, W, H, hd = 16, 16, 16, 2, 128
    case = _chunk_case(b, 9, 40, bs, W, H, H, hd, "float32")
    monkeypatch.setattr(fa, "_PAGED_CHUNK_VMEM", 1000)
    assert "VMEM" in fa.paged_chunk_gate(b, W * bs, bs, H * hd, H * hd, H,
                                         4, True)
    assert _calls(lambda *a: fa.paged_flash_chunk_attention(
        *a, num_heads=H), *case) == {"flash_attention_paged_decode": 1}
    out, single, _ = _three_ways(*case, H, H)
    np.testing.assert_array_equal(out, single)


def test_query_tiles_under_grouped_heads_are_whole_lane_tiles():
    """The grouped body stacks a KV head's (head, row) pairs along the
    lanes: `group` x the tile's rows are whole 128-lane tiles, a pass
    takes whole heads and whole tiles of them, 512 rows at the most."""
    for group in (2, 3, 4, 8, 16):
        for b in (1, 8, 16, 40, 64, 100, 128, 256):
            tq, tiles = fa._paged_chunk_query_tile(b, group)
            assert tq <= 128 and tq * tiles >= b and tq % 16 == 0
            assert group * tq % 128 == 0
            block = fa._paged_chunk_block_lanes(group, tq)
            assert (block % 128 == 0 and block % tq == 0 and block <= 512
                    and group * tq % block == 0)
    assert fa._paged_chunk_query_tile(256, 16) == (128, 2)
    assert fa._paged_chunk_query_tile(8, 8) == (16, 1)
    assert fa._paged_chunk_query_tile(8, 2) == (64, 1)
    assert fa._paged_chunk_block_lanes(16, 128) == 512
    assert fa._paged_chunk_block_lanes(3, 128) == 384


def test_query_tiles_and_the_rows_they_pad():
    assert fa._paged_chunk_query_tile(1) == (16, 1)
    assert fa._paged_chunk_query_tile(16) == (16, 1)
    assert fa._paged_chunk_query_tile(24) == (32, 1)
    assert fa._paged_chunk_query_tile(128) == (128, 1)
    assert fa._paged_chunk_query_tile(192) == (128, 2)
    assert fa._paged_chunk_query_tile(256) == (128, 2)


# ------------------------------------------------------------------ the op

def _op_call(chunk_from, rows, kv_heads, dtype="float32", impl="flash",
             **front):
    """A (rows, 1) call of the paged attention op over a pool that holds
    every slot's past: the slots' rows decode at their own lengths, rows
    past `slots` are a chunk of slot 1 (the last two of them dead).
    `front`: what else the layer's front end has (a window, a sink)."""
    H, hd, bs, W, slots = 4, 32, 16, 12, 4
    E = H * hd
    front = AttentionFrontEnd(E, H, use_bias=False, num_kv_heads=kv_heads,
                              **front)
    p = inc.PagedIncMultiHeadAttentionParams(
        front, W * bs, bs, 1 + slots * W, impl=impl,
        cache_dtype=DataType.DT_FLOAT, chunk_from=chunk_from)
    op = get_op_def(OT.OP_PAGED_INC_MULTIHEAD_ATTENTION)
    rs = np.random.RandomState(rows)
    dt = jnp.dtype(dtype)
    specs = op.weights(p, [(rows, 1, E), (rows, 1), (rows, W)])
    weights = {w.name: jnp.asarray(rs.randn(*w.shape) * 0.1, jnp.float32)
               for w in specs}
    table = np.arange(1, 1 + slots * W, dtype=np.int32).reshape(slots, W)
    lengths = np.asarray([150, 70, 0, 33], np.int32)
    positions = np.where(lengths > 0, lengths, W * bs)
    start, b = 70, rows - slots
    if b:
        positions[1] = W * bs  # the prefilling slot's own row is dead
        chunk_pos = np.where(np.arange(b) < b - 2, start + np.arange(b),
                             W * bs)
        positions = np.r_[positions, chunk_pos]
        table = np.r_[table, np.repeat(table[1:2], b, axis=0)]
    x = jnp.asarray(rs.randn(rows, 1, E), dt)
    inputs = [x, jnp.asarray(positions, jnp.int32)[:, None],
              jnp.asarray(table)]
    return p, op, inputs, weights


def _op_calls(p, op, inputs, weights) -> dict:
    return _calls(lambda *a: op.forward(
        p, list(a), weights, None, OpContext(training=False, mesh=None)),
        *inputs)


def _forward(p, op, inputs, weights):
    (y,), state = op.forward(p, inputs, weights, None,
                             OpContext(training=False, mesh=None))
    return np.asarray(y, np.float32), state


@pytest.mark.parametrize("window", [0, 24], ids=["global", "window"])
@pytest.mark.parametrize("kv_heads", [4, 1], ids=["mha", "grouped"])
def test_op_sends_rows_past_chunk_from_through_one_chunk_call(kv_heads,
                                                              window):
    """A (chunk_from + b, 1) call: the slots' rows through the
    single-query kernel, the chunk's through ONE call of the chunk kernel
    (a window layer's under its window walk, 24 keys of a chunk at 70),
    and the result is what the single-query kernel gives row by row (the
    op without `chunk_from`), pool writes included."""
    rows = 4 + 16
    p, op, inputs, weights = _op_call(4, rows, kv_heads, window=window)
    names = _op_calls(p, op, inputs, weights)
    g = ("_window" if window else "") + ("_grouped" if kv_heads == 1 else "")
    assert names == {"flash_attention_paged_decode" + g: 1,
                     "flash_attention_paged_chunk" + g: 1}
    y, state = _forward(p, op, inputs, weights)
    p0, op, inputs, weights = _op_call(None, rows, kv_heads, window=window)
    assert _op_calls(p0, op, inputs, weights) == {"flash_attention_paged_decode" + g: 1}
    y0, state0 = _forward(p0, op, inputs, weights)
    assert np.isfinite(y).all()
    np.testing.assert_allclose(y, y0, rtol=2e-5, atol=2e-5)
    for leaf in ("pool_k", "pool_v"):
        np.testing.assert_array_equal(np.asarray(state[leaf]),
                                      np.asarray(state0[leaf]))


@pytest.mark.parametrize("kv_heads", [4, 1], ids=["mha", "grouped"])
def test_op_call_of_chunk_from_rows_is_what_it_was(kv_heads):
    """A pure-decode call (rows == chunk_from) is byte-identical with and
    without the field: one single-query call over all the rows."""
    p, op, inputs, weights = _op_call(4, 4, kv_heads)
    g = "_grouped" if kv_heads == 1 else ""
    assert _op_calls(p, op, inputs, weights) == {"flash_attention_paged_decode" + g: 1}
    y, _ = _forward(p, op, inputs, weights)
    p0, op, inputs, weights = _op_call(None, 4, kv_heads)
    y0, _ = _forward(p0, op, inputs, weights)
    np.testing.assert_array_equal(y, y0)


def test_op_keeps_one_single_query_call_where_the_chunk_gate_refuses(
        monkeypatch):
    """Refused rows are not split off: one call of the single-query
    kernel over slots and chunk rows alike, as before."""
    monkeypatch.setattr(fa, "_PAGED_CHUNK_VMEM", 1000)
    p, op, inputs, weights = _op_call(4, 4 + 16, 4)
    assert inc.paged_chunk_query_tile(p, None, 4, 16) is None
    assert _op_calls(p, op, inputs, weights) == {"flash_attention_paged_decode": 1}


def test_the_engines_question_has_the_ops_answer():
    """`paged_chunk_query_tile` is asked by the engine a bucket: the tile
    where the op would make the chunk call, None where it would not (no
    `chunk_from`, no kernel asked for, more than one device). A window
    layer answers as the global layer beside it does (one answer a graph:
    ServingEngine._chunk_query_tile); under a sink the tile loop in XLA
    reads the context once for all of the chunk's rows."""
    p, *_ = _op_call(4, 4 + 16, 4)
    assert inc.paged_chunk_query_tile(p, None, 4, 16) == 16
    assert inc.paged_chunk_query_tile(p, None, 4, 256) == 128
    for kv_heads in (4, 1):
        pg, *_ = _op_call(4, 4 + 16, kv_heads)
        pw, *_ = _op_call(4, 4 + 16, kv_heads, window=24)
        assert not pw.front.plain_core
        for b in (16, 64, 256):
            assert (inc.paged_chunk_query_tile(pw, None, 4, b)
                    == inc.paged_chunk_query_tile(pg, None, 4, b)
                    == fa._paged_chunk_query_tile(b, 4 // kv_heads)[0])
    ps, *_ = _op_call(4, 4 + 16, 4, window=24, sink=True)
    assert inc.paged_chunk_query_tile(ps, None, 4, 16) == 16
    assert inc.paged_chunk_query_tile(ps, None, 4, 256) == 256
    assert "sink" in inc._chunk_gate(ps, 16, 4)
    p0, *_ = _op_call(None, 4 + 16, 4)
    assert inc.paged_chunk_query_tile(p0, None, 4, 16) is None
    pe, *_ = _op_call(4, 4 + 16, 4, impl="einsum")
    assert inc.paged_chunk_query_tile(pe, None, 4, 16) is None


# -------------------------------------------------------------- the engine

ROWS_ENGINE = dict(slots=2, max_new_tokens=3, prefill_chunk=4,
                   prefix_sharing=False, **ROWS)


def _chunk_spans(eng, prompts, monkeypatch):
    """The `serve.prefill` span arguments of the chunk steps `prompts`
    take, as `_schedule` makes them."""
    spans, schedule = [], eng._schedule

    def spy():
        step = schedule()
        if step is not None and step.chunk is not None:
            spans.append(step.span[1])
        return step

    monkeypatch.setattr(eng, "_schedule", spy)
    out = eng.generate(prompts)
    return out, spans


PROMPTS = [[3, 7, 11, 2, 5], [5, 2]]


def test_engine_counts_the_steps_the_chunk_kernel_took(monkeypatch):
    """Every chunk step laid out as rows had its chunk's rows in one call
    of the chunk kernel, and its span counts the context rows once."""
    eng = engine(build_rows_lm(), **ROWS_ENGINE)
    assert eng._chunk_rows and eng._chunk_query_tile(4) == 16
    out, spans = _chunk_spans(eng, PROMPTS, monkeypatch)
    st = eng.stats()
    assert st["chunk_kernel_steps"] == st["row_steps"] == 3
    assert [s["kv_rows_walked"] for s in spans] == [
        s["kv_rows"] for s in spans] == [4, 5, 8]
    # what the single-query kernel's walk copies (whole pages of 16 rows)
    # and the live rows whose first round the live row before started: no
    # slot decodes beside the first prompt's chunks; its 6 rows beside the
    # second's chunk; then slots of 7 and 3 rows, and the second's 4 alone
    assert eng._walk_block == 16
    assert [(s["kv_rows_copied"], s["rows_handed"]) for s in spans] == [
        (0, 0), (0, 0), (16, 0)]
    assert (st["kv_rows_copied"], st["rows_handed"]) == (16 + 32 + 16, 1)
    eng.reset_stats()
    assert eng.stats()["chunk_kernel_steps"] == 0
    assert "kv_rows_copied" not in eng.stats()  # counted from a step on


def test_window_layers_beside_a_global_one_give_the_engine_one_answer(
        monkeypatch):
    """Three window layers and a global one (Command A+'s order, 8 query
    heads on 2 KV heads, a window of 8): every layer's chunk rows go
    through the chunk kernel, so the graph has ONE query tile, the steps
    are counted and a span counts a tile's context once; the tokens are
    those of the engine on the gather-and-einsum path."""
    from test_command_a_plus import build

    ff = build(seq=128, batch=1)
    kw = dict(slots=2, max_new_tokens=3, max_seq_len=128, prefill_chunk=4,
              prefix_sharing=False, kv_layout="paged", kv_block_size=16)
    prompts = [[3, 7, 11, 2, 5, 9, 4, 8, 1, 6, 2, 3], [5, 2]]
    want = engine(ff, **kw).generate(prompts)
    eng = engine(ff, impl="flash", **kw)
    tiles = {s.chunk_query_tile(None, 4, 4)
             for group in eng._groups for s in group.values()}
    assert tiles == {32} and len(eng._groups) == 2
    assert eng._chunk_rows and eng._chunk_query_tile(4) == 32
    out, spans = _chunk_spans(eng, prompts, monkeypatch)
    assert out == want
    st = eng.stats()
    assert st["chunk_kernel_steps"] == st["row_steps"] == len(spans) >= 3
    assert [s["kv_rows_walked"] for s in spans] == [
        s["kv_rows"] for s in spans]


def test_engine_runs_refused_chunks_through_the_single_query_kernel(
        monkeypatch):
    """A bucket the chunk kernel's gate refuses: the chunk still rides as
    rows, through the single-query kernel, the count stays 0, the span
    says what those rows walk, and the tokens are the same."""
    ff = build_rows_lm()
    want = engine(ff, **ROWS_ENGINE).generate(PROMPTS)
    monkeypatch.setattr(fa, "_PAGED_CHUNK_VMEM", 1000)
    # serve(): its buckets are traced under the budget patched above
    eng = ff.serve(**ROWS_ENGINE)
    assert eng._chunk_rows and eng._chunk_query_tile(4) is None
    out, spans = _chunk_spans(eng, PROMPTS, monkeypatch)
    assert out == want
    st = eng.stats()
    assert (st["chunk_kernel_steps"], st["row_steps"]) == (0, 3)
    # chunk row i walks start + i + 1 rows: 1+2+3+4; 5; (1+2) beside the
    # first request's 6
    assert [s["kv_rows_walked"] for s in spans] == [10, 5, 9]
    assert [s["kv_rows"] for s in spans] == [4, 5, 8]
    # each of those rows copies its own page and, behind a live row, is
    # handed its first round: 4 rows; 1; the first slot's and the chunk's 2
    assert [(s["kv_rows_copied"], s["rows_handed"]) for s in spans] == [
        (64, 3), (16, 0), (48, 1)]
