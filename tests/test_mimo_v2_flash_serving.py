"""MiMo-V2-Flash served through the cache of two groups at a small size
(PR 41): prefill in chunks and then decode through the paged pools (the
global group every row, the window group the blocks of a slot's last
rows) against the float32 reference's full forward, logits, with sequences
several windows and several blocks long; the engine's own admission,
freeing, prefix match and copy-on-write over both groups; the spans and
counters. tests/test_mimo_v2_flash.py has the model, tests/
test_window_cache_groups.py the block manager and the kernel alone.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.fftype import OperatorType as OT
from flexflow_tpu.models import mimo_v2_flash_reference as ref

from small_lms import engine
from test_mimo_v2_flash import SEQ, TINY, TOL, build, error, getter


@pytest.fixture(scope="module")
def model():
    return build()


def serve(ff, **kw):
    """The shared engine of these options (tests/small_lms.py), as new."""
    return engine(ff, **{**dict(slots=3, max_seq_len=SEQ, prefill_chunk=8,
                                kv_block_size=4, kv_num_blocks=48), **kw})


def is_greedy(ff, prompt, reply) -> bool:
    """Whether `reply` is the reference's greedy continuation of `prompt`:
    one forward over both (a row's logits depend on no later token), each
    reply token the argmax of the row before it."""
    logits, _ = ref.forward(getter(ff), [*prompt, *reply[:-1]], TINY)
    return np.argmax(logits[len(prompt) - 1:], axis=-1).tolist() == reply


def decode_graph_logits(eng, seq, split, slot=1, held=None):
    """Logits of `seq` through the decode graph: the first `split` tokens
    in the engine's chunks as rows past the slots (or in the slot's row of
    the rectangle where the engine lays a chunk out so), the rest decoded
    one a step in `slot`, through hand-made tables of both groups. `held`:
    logical blocks the window group's table keeps (the others point at the
    scratch block, as the block manager leaves what fell behind a
    window)."""
    dec, ex = eng.decode_model, eng.decode_model.executor
    slots, dead = eng.spec.slots, eng.max_seq_len
    chunk = eng.spec.prefill_chunk
    W = eng.block_manager.table_width
    table = (1 + np.arange(slots * W, dtype=np.int32)).reshape(slots, W)
    by_rows = eng._chunk_rows

    @jax.jit
    def step(params, state, xs):
        logits, new_state, _ = ex._apply(params, state, ex._cast_compute(xs),
                                         training=False, rng=None)
        return ex._restore_state_dtypes(new_state), logits

    def call(toks, positions, row_slots, at):
        window = table.copy()
        if held is not None:
            keep = held(at)
            window[:, [b for b in range(W) if b not in keep]] = 0
        xs = {"tokens": jnp.asarray(toks), "positions": jnp.asarray(positions),
              "page_table": jnp.asarray(table[row_slots]),
              "page_table_w": jnp.asarray(window[row_slots])}
        dec._state, rows = step(dec._params, dec._state, xs)
        return np.asarray(rows)

    out = []
    for start in range(0, split, chunk):
        part = seq[start:min(start + chunk, split)]
        at = np.arange(start, start + len(part))
        if by_rows:
            toks = np.zeros((slots + chunk, 1), np.int32)
            positions = np.full((slots + chunk, 1), dead, np.int32)
            toks[slots:slots + len(part), 0] = part
            positions[slots:slots + len(part), 0] = at
            rows = call(toks, positions,
                        np.r_[np.arange(slots), np.full(chunk, slot)], start)
            out += list(rows[slots:slots + len(part), 0])
        else:
            toks = np.zeros((slots, chunk), np.int32)
            positions = np.full((slots, chunk), dead, np.int32)
            toks[slot, :len(part)], positions[slot, :len(part)] = part, at
            rows = call(toks, positions, np.arange(slots), start)
            out += list(rows[slot, :len(part)])
    for t in range(split, len(seq)):
        toks = np.zeros((slots, 1), np.int32)
        positions = np.full((slots, 1), dead, np.int32)
        toks[slot, 0], positions[slot, 0] = seq[t], t
        out.append(call(toks, positions, np.arange(slots), t)[slot, 0])
    return np.stack(out)


def test_the_decode_graph_has_a_table_and_a_pool_size_a_group(model):
    eng = serve(model, kv_window_blocks=21)
    dec = eng.decode_model
    names = {t.name for t in dec._input_tensors}
    assert {"page_table", "page_table_w"} <= names
    ops = {l.name: l for l in dec.layers
           if l.op_type == OT.OP_PAGED_INC_MULTIHEAD_ATTENTION}
    assert {n: (p.params.num_blocks, p.inputs[2].name)
            for n, p in ops.items()} == {
        "l0_attn": (48, "page_table"), "l1_attn": (21, "page_table_w"),
        "l2_attn": (21, "page_table_w"), "l3_attn": (48, "page_table")}
    state = dec._state
    assert state["l0_attn"]["pool_k"].shape == (48, 4, 24)
    assert state["l0_attn"]["pool_v"].shape == (48, 4, 16)
    assert state["l1_attn"]["pool_k"].shape == (21, 4, 48)
    assert state["l1_attn"]["pool_v"].shape == (21, 4, 32)
    mgr = eng.block_manager
    assert mgr.window.num_blocks == 21 and mgr.window.window == 6
    # the window before a chunk of 8 and the chunk: 13 rows = 4 blocks, and
    # two of slack
    assert mgr.window.slot_blocks == 6
    assert eng._window_nodes == ["l1_attn", "l2_attn"]
    # bytes a block holds over a group's layers: 2 x 4 x 40 x 4 B global,
    # 2 x 4 x 80 x 4 B window
    assert eng._block_bytes == (1280, 2560)
    # unpinned, the window group is sized from the slots, the global group
    # from what is left (here: capacity parity, the CPU's budget is large)
    assert serve(model, kv_num_blocks=0).block_manager.window.num_blocks == 31


def test_pool_blocks_are_priced_by_group(model, monkeypatch):
    from flexflow_tpu.fftype import DataType
    from flexflow_tpu.search import machine_model
    from flexflow_tpu.serving import decode_graph

    class Chip:
        hbm_bytes = 0

    monkeypatch.setattr(
        machine_model, "machine_model_for_mesh",
        lambda mesh, **kw: type("M", (), {"chip": Chip, "num_hosts": 1})())
    weights = sum(w.size * 4 for ws in model._params.values()
                  for w in ws.values())
    spec = decode_graph.ServingSpec(slots=2, kv_block_size=4,
                                    prefill_chunk=8, kv_window_blocks=10)
    # 100 global blocks of 2 layers x 4 rows x 40 numbers x 4 B beside 10
    # window blocks of 2 layers x 4 rows x 80 numbers
    Chip.hbm_bytes = (weights + 100 * 1280 + 10 * 2560 + 8) / 0.9
    assert decode_graph.resolve_pool_blocks(
        model, spec, 4000, DataType.DT_FLOAT) == (100, 10)
    spec.kv_window_blocks = 0   # 2 x 2 slots x 6 blocks + scratch
    assert decode_graph.resolve_pool_blocks(
        model, spec, 4000, DataType.DT_FLOAT)[1] == 25


@pytest.mark.parametrize("split", [30, 9])
def test_prefill_in_chunks_then_decode_is_the_references_forward(model,
                                                                 split):
    """A sequence of 40 = six windows and ten blocks: every row's logits
    through the two-group cache, prefilled in chunks of 8 to `split` and
    decoded from there, against the reference's full forward."""
    seq = np.random.default_rng(2).integers(0, 97, SEQ).tolist()
    want, _ = ref.forward(getter(model), seq, TINY)
    got = decode_graph_logits(serve(model), seq, split)
    assert error(got, want) < TOL


def test_the_window_group_needs_only_the_blocks_of_the_window(model):
    """The same, with the window group's table holding, at every step,
    only the blocks from the one of row `position - 5` on: what fell
    behind points at the scratch block and changes no logit; with one
    block too few the logits are off."""
    seq = np.random.default_rng(3).integers(0, 97, SEQ).tolist()
    want, _ = ref.forward(getter(model), seq, TINY)
    eng = serve(model)
    first = eng.block_manager.window.first_block
    got = decode_graph_logits(
        eng, seq, 30, held=lambda at: set(range(first(at), 10)))
    assert error(got, want) < TOL
    lost = decode_graph_logits(
        serve(model), seq, 30,
        held=lambda at: set(range(first(at) + 1, 10)))
    assert error(lost, want) > 1e-3


def generated(model):
    """An engine that served three prompts, held to the reference's greedy
    continuation and to what the groups hold after."""
    rng = np.random.default_rng(1)
    eng = serve(model)
    prompts = [rng.integers(0, 97, n).tolist() for n in (21, 9, 30)]
    out = eng.generate(prompts, max_new_tokens=8)
    for p, o in zip(prompts, out):
        assert len(o) == 8 and is_greedy(model, p, o)
    mgr = eng.block_manager
    mgr.window.check_invariants()
    st = eng.stats()
    # a slot never held more than its share, and blocks were freed behind
    # the slots as they advanced
    assert 0 < st["kv_window_blocks_in_use_peak"] <= 3 * mgr.window.slot_blocks
    assert st["window_blocks_freed"] > 0
    assert st["kv_window_blocks_in_use"] == st["kv_blocks_in_use"] == 0
    # what the cache still holds: every prompt's blocks in the global
    # group, the blocks of its last rows in the window group
    assert st["kv_blocks_held"] == sum(-(-len(p) // 4) for p in prompts)
    assert 0 < st["kv_window_blocks_held"] < st["kv_blocks_held"]
    assert st["kv_pool_bytes"] == (st["kv_blocks_held"] * 1280
                                   + st["kv_window_blocks_held"] * 2560)
    assert st["kv_cached_tokens"] == st["kv_blocks_held"] * 4
    # a held token costs less than every layer's row (4 x ... = 960 B)
    assert st["kv_pool_bytes"] / st["kv_cached_tokens"] < 960
    return eng, prompts


def test_generate_is_the_references_greedy_continuation(model):
    generated(model)


def test_a_prompt_hits_its_cached_prefix_over_both_groups(model):
    eng, prompts = generated(model)
    mgr = eng.block_manager
    before = eng.stats()["prefix_hit_tokens"]
    follow = prompts[0] + [5, 6, 7]
    out = eng.generate([follow], max_new_tokens=4)
    assert len(out[0]) == 4 and is_greedy(model, follow, out[0])
    assert eng.scheduler.completed[-1].matched_prefix_len == 21
    assert eng.stats()["prefix_hit_tokens"] - before == 21
    # the shared tail block (rows 20..) was copied in both groups
    assert eng.stats()["window_cow_copies"] >= 1
    # a prompt that parts from a cached one where the window group holds
    # nothing (row 12 of 21: only the last rows' blocks were kept) matches
    # nothing, whatever the global group holds
    assert mgr.cache.match(prompts[0][:12] + [1], peek=True)[0] == 12
    assert mgr.match_prefix(prompts[0][:12] + [1]) == 0
    mgr.window.check_invariants()


def test_the_steps_span_says_what_the_window_layers_read(model, monkeypatch):
    from flexflow_tpu import telemetry

    seen = []
    real = telemetry.span

    def span(name, **args):
        if name in ("serve.step", "serve.prefill"):
            seen.append((name, args))
        return real(name, **args)

    monkeypatch.setattr(telemetry, "span", span)
    eng = serve(model)
    eng.generate([list(range(1, 20))], max_new_tokens=3)
    steps = [a for n, a in seen if n == "serve.step"]
    chunks = [a for n, a in seen if n == "serve.prefill"]
    # a decode row at position 19 reads 20 context rows in a global layer
    # and its window of 6 in a window layer
    assert (steps[0]["kv_rows"], steps[0]["window_rows"]) == (20, 6)
    # the first chunk's rows 0..7 read 1, 2, .., 6, 6, 6 rows
    assert chunks[0]["window_rows"] == 1 + 2 + 3 + 4 + 5 + 6 + 6 + 6
    assert chunks[1]["window_rows"] == 8 * 6


def test_the_scopes_name_the_two_kinds_of_core(model):
    eng = serve(model)
    dec, slots = eng.decode_model, eng.spec.slots
    xs = eng._stage_inputs(np.zeros((slots, 1), np.int32),
                           np.full((slots, 1), SEQ, np.int32))
    text = eng._step_fn.lower(
        dec._params, dec._state, xs, jnp.zeros((slots,), jnp.int32),
        jax.random.key(0), jnp.zeros((slots,), jnp.float32)).as_text(
            debug_info=True)
    assert "l1_attn/swa.attend" in text and "l0_attn/gqa.attend" in text
    assert "l1_attn/gqa.attend" not in text
    assert "l0_attn/swa.attend" not in text


def test_the_contiguous_layout_serves_under_the_band(model):
    """No window group there: the cache holds every row, the einsum masks
    the band. Said in docs/serving.md."""
    eng = engine(model, slots=2, max_seq_len=SEQ, prefill_chunk=8,
                 kv_layout="contiguous")
    prompt = np.random.default_rng(4).integers(0, 97, 17).tolist()
    (reply,) = eng.generate([prompt], max_new_tokens=6)
    assert len(reply) == 6 and is_greedy(model, prompt, reply)
    state = eng.decode_model._state["l1_attn"]
    assert state["cache_k"].shape == (2, SEQ + 1, 48)
    assert state["cache_v"].shape == (2, SEQ + 1, 32)
