"""Serving-engine tests (serving/, docs/serving.md).

The acceptance surface of the decode-graph + continuous-batching
subsystem, on the CPU mesh (the decode attention op routes through the
reference einsum there, so everything below is Pallas-free except the
kernel-parity tests, which run the kernels in interpret mode):

  - greedy decode is token-identical to the teacher-forced training
    forward's argmax at every generated position;
  - an interleaved continuous batch (requests admitted/evicted mid-run)
    is token-identical to serving each request alone;
  - the KV cache round-trips a tensor-parallel mesh: a head-parallel plan
    shards the cache feature dim over `model` and decode stays
    token-identical to the single-device engine;
  - EOS / max_new_tokens / cache-capacity completion all fire with the
    right reasons;
  - a second serving compile of the same (model, slots, max_seq, mesh)
    against one --warmstart-dir is a plan-cache hit: ZERO
    UnitySearch.evaluate calls, zero joint_graph_optimize calls.
"""

import numpy as np
import pytest

from small_lms import (
    PROMPTS, ROWS, SearchSpy, build_lm, build_rows_lm, engine, lm_config,
    new_lm, staged_shapes,
)


def _teacher_argmax(ff, sequence):
    """Training-graph forward over `sequence`; argmax at every position."""
    import jax

    T = len(sequence)
    toks = np.asarray(sequence, np.int32)[None, :]
    pos = np.arange(T, dtype=np.int32)[None, :]
    fwd = ff.executor._forward_fn or ff.executor.build_forward()
    xs = ff.executor.shard_batch({"tokens": toks, "positions": pos}, {})
    logits, _ = fwd(ff._params, ff._state, xs, False)
    return np.asarray(jax.device_get(logits)).argmax(-1)[0]


def test_greedy_decode_parity_vs_teacher_forced():
    """Every greedy-decoded token equals the training forward's argmax at
    that position, for prompts long and short of the prefill chunk (so
    both the bucketed prefill and the q=1 decode path are checked)."""
    ff = build_lm(batch=1)
    eng = engine(ff, slots=2, max_new_tokens=8, prefill_chunk=4)
    for prompt in PROMPTS:
        (gen,) = eng.generate([prompt])
        assert len(gen) == 8
        seq = prompt + gen
        am = _teacher_argmax(ff, seq)
        want = am[len(prompt) - 1 : len(seq) - 1].tolist()
        assert gen == want, f"prompt {prompt}: decode {gen} != teacher {want}"


@pytest.mark.parametrize("layout", ["rectangle", "rows"])
def test_continuous_batching_invariance(layout, monkeypatch):
    """Interleaved batch == sequential single-request runs, token for
    token. Six requests through two slots forces mid-run admission and
    slot reuse (stale cache rows from the previous resident must never
    leak into the next request); prompts shorter than, equal to and
    several times the chunk, so decoding slots ride chunk steps of every
    bucket. In the rows layout also: every chunk step was laid out as
    rows, the tokens are the rectangle engine's and the teacher-forced
    forward's, and the rectangle engine staged (slots, q) calls only."""
    rows = layout == "rows"
    ff = build_rows_lm() if rows else build_lm(batch=1)
    kw = dict(slots=2, max_new_tokens=6, prefill_chunk=4,
              **(ROWS if rows else {}))
    prompts = PROMPTS + [[2, 4, 6, 8], list(range(1, 17))]

    eng = engine(ff, **kw)
    assert eng._chunk_rows == rows
    shapes = staged_shapes(eng, monkeypatch)
    interleaved = eng.generate(prompts)
    assert eng.scheduler.drained
    # two slots, six requests: admissions happened while others decoded
    st = eng.stats()
    assert st["requests_completed"] == len(prompts)
    W = eng.block_manager.table_width
    if rows:
        assert st["row_steps"] == st["prefill_calls"] > 0
        # and each chunk's rows were one call of the paged chunk kernel
        assert st["chunk_kernel_steps"] == st["row_steps"]
        assert set(shapes) == {((2 + b, 1), (2 + b, W)) for b in (0, 1, 2, 4)}
    else:
        assert st["row_steps"] == 0
        assert set(shapes) == {((2, q), (2, W)) for q in (1, 2, 4)}

    eng = engine(ff, **kw)     # as new: the interleaved run's blocks are gone
    solo = [eng.generate([p])[0] for p in prompts]
    assert interleaved == solo
    if rows:
        rect = engine(ff, **{**kw, "impl": "xla"})
        assert not rect._chunk_rows
        assert rect.generate(prompts) == interleaved
        assert rect.stats()["row_steps"] == 0
        for prompt, gen in zip(prompts, interleaved):
            seq = prompt + gen
            want = _teacher_argmax(ff, seq)[len(prompt) - 1:len(seq) - 1]
            assert gen == want.tolist()


def test_kv_cache_sharding_roundtrip_tp_mesh():
    """A head-parallel decode plan on a (data=2, model=2) mesh — QKV/O
    sharded, KV cache feature dim over `model` — produces token-identical
    output to the single-device engine for BOTH layouts, and the cache
    state actually carries the sharded spec (contiguous: slot dim over
    `data` too; paged: the pool's block dim stays whole — blocks are
    shared across slots by prefix reuse)."""
    from jax.sharding import PartitionSpec as P

    def attn_strategy(cache_weights):
        strat = {}
        for i in range(2):
            strat[f"l{i}_attn"] = {"outputs": {}, "weights": {
                "wq": P(None, "model"), "wk": P(None, "model"),
                "wv": P(None, "model"),
                "bq": P("model"), "bk": P("model"), "bv": P("model"),
                "wo": P("model", None), "bo": P(),
                **cache_weights,
            }}
        return strat

    ff1 = build_lm(mesh=(1, 1, 1, 1), batch=1)
    want = engine(ff1, slots=4, max_new_tokens=5,
                  prefill_chunk=4).generate(PROMPTS[:2])

    ff = build_lm(mesh=(2, 2, 1, 1), batch=8)
    # serve(), here and for the contiguous layout below: a strategy of
    # dicts is no key for the shared engines
    eng = ff.serve(slots=4, max_new_tokens=5, prefill_chunk=4,
                   strategy=attn_strategy({
                       "pool_k": P(None, None, "model"),
                       "pool_v": P(None, None, "model")}))
    assert eng.decode_model._plan_source == "manual"
    pk = eng.decode_model._state["l0_attn"]["pool_k"]
    assert pk.sharding.spec == P(None, None, "model")
    # feature dim over model=2: each chip holds only its heads' pool
    assert pk.sharding.shard_shape(pk.shape)[-1] == pk.shape[-1] // 2
    assert eng.generate(PROMPTS[:2]) == want

    engc = ff.serve(slots=4, max_new_tokens=5, prefill_chunk=4,
                    kv_layout="contiguous",
                    strategy=attn_strategy({
                        "cache_k": P("data", None, "model"),
                        "cache_v": P("data", None, "model")}))
    ck = engc.decode_model._state["l0_attn"]["cache_k"]
    assert ck.sharding.spec == P("data", None, "model")
    # 4 slots over data=2: the contiguous slot dim is genuinely sharded
    assert ck.sharding.shard_shape(ck.shape)[0] == 2
    assert engc.generate(PROMPTS[:2]) == want


def test_eos_and_max_len_completion():
    """All three completion rules: eos (stop token sampled), max_tokens
    (budget), and length (KV cache full)."""
    ff = build_lm(batch=1)
    eng = engine(ff, slots=2, max_new_tokens=10, prefill_chunk=4)
    prompt = PROMPTS[0]
    # discover what greedy generates, then replay with its second token
    # as the stop token
    (gen,) = eng.generate([prompt])
    eos = gen[1]
    req = eng.submit(prompt, eos_id=eos)
    eng.run_until_drained()
    assert req.finished and req.finish_reason == "eos"
    assert req.generated[-1] == eos and len(req.generated) == 2

    req2 = eng.submit(prompt, max_new_tokens=3)
    eng.run_until_drained()
    assert req2.finish_reason == "max_tokens"
    assert len(req2.generated) == 3 and req2.generated == gen[:3]

    # cache capacity: prompt of 6 into an 8-row cache leaves room to feed
    # 2 generated tokens back; the 3rd sampled token cannot be fed
    small = engine(ff, slots=2, max_new_tokens=10, prefill_chunk=4,
                   max_seq_len=8)
    req3 = small.submit([1, 2, 3, 4, 5, 6])
    small.run_until_drained()
    assert req3.finish_reason == "length"
    assert len(req3.generated) == 3
    # oversized prompts are rejected at submission
    with pytest.raises(ValueError):
        small.submit(list(range(9)))


def test_serving_warmstart_plan_cache_hit(tmp_path):
    """Second serving compile of the same (model, slots, max_seq, mesh)
    against one --warmstart-dir: plan_source=cache, 0 evaluate calls,
    0 searches, and token-identical output (the acceptance criterion)."""
    ws = str(tmp_path / "ws")
    ff = build_lm(mesh=(2, 4, 1, 1), batch=8,
                  argv=["--only-data-parallel"])
    ov = dict(only_data_parallel=False, search_budget=4,
              enable_parameter_parallel=True,
              enable_attribute_parallel=True, warmstart_dir=ws)
    kw = dict(slots=8, max_new_tokens=4, prefill_chunk=4,
              config_overrides=ov)
    # serve() throughout: each compile's plan source is what is asserted
    eng1 = ff.serve(**kw)
    assert eng1.decode_model._plan_source == "search"
    out1 = eng1.generate(PROMPTS[:2])

    with SearchSpy() as spy:
        eng2 = ff.serve(**kw)
    assert spy.searches == 0, "serving plan-cache hit must not re-search"
    assert spy.evals == 0, "serving plan-cache hit must cost 0 evaluations"
    assert eng2.decode_model._plan_source == "cache"
    assert eng2.generate(PROMPTS[:2]) == out1

    # a different bucket geometry (slots) is a different decode graph —
    # it must NOT be served by the cached plan
    with SearchSpy() as spy:
        eng3 = ff.serve(slots=4, max_new_tokens=4, prefill_chunk=4,
                        config_overrides=ov)
    assert eng3.decode_model._plan_source == "search"
    assert spy.searches == 1


def test_serving_telemetry_artifacts(tmp_path):
    """With a telemetry session attached, serving emits the serve.compile
    event (plan_source), per-request serve.request events with TTFT, and
    a serve.summary with requests/s/chip + decode tokens/s/chip."""
    ff = new_lm(batch=1)    # its own: the session is the model's
    ff.enable_telemetry(str(tmp_path / "tel"))
    eng = ff.serve(slots=2, max_new_tokens=4, prefill_chunk=4)
    eng.generate(PROMPTS[:3])
    eng.telemetry.close()

    from flexflow_tpu.telemetry import read_jsonl

    recs = read_jsonl(str(tmp_path / "tel" / "metrics.jsonl"))
    compiles = [r for r in recs if r["kind"] == "serve.compile"]
    assert compiles and compiles[0]["plan_source"] == "default"
    assert compiles[0]["slots"] == 2
    reqs = [r for r in recs if r["kind"] == "serve.request"]
    assert len(reqs) == 3
    for r in reqs:
        assert r["ttft_s"] > 0 and r["new_tokens"] == 4
        assert r["finish_reason"] == "max_tokens"
    summaries = [r for r in recs if r["kind"] == "serve.summary"]
    assert summaries
    s = summaries[-1]
    assert s["requests_per_sec_per_chip"] > 0
    assert s["decode_tokens_per_sec_per_chip"] > 0
    assert s["requests_completed"] == 3

    import json

    with open(tmp_path / "tel" / "trace.json") as f:
        names = {e["name"] for e in json.load(f)["traceEvents"]}
    for span in ("serve.compile", "serve.prefill", "serve.step"):
        assert span in names, f"trace missing {span!r}"


@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_decode_replay_signature(layout):
    """The replay (serving/decode_graph.py, the one way a decode graph is
    made) keeps the training graph's node names, turns each attention
    node into the layout's decode op around the trained layer's own front
    end, and sizes the cache at capacity parity: a scratch row a slot, or
    slots * ceil(max_seq / block) blocks and the scratch block."""
    from flexflow_tpu.fftype import OperatorType as OT
    from flexflow_tpu.serving import ServingSpec, build_decode_model

    c = lm_config()
    ff = build_lm(batch=1)
    dec, max_seq = build_decode_model(
        ff, ServingSpec(slots=2, kv_layout=layout))
    assert max_seq == c.sequence_length == 32

    train = ff.graph.topo_order()
    nodes = dec.graph.topo_order()
    # the one node the replay adds: the page tables, an input
    extra = [n.name for n in nodes if n.op_type == OT.OP_INPUT
             and n.name not in ("tokens", "positions")]
    assert extra == (["page_table"] if layout == "paged" else [])
    nodes = [n for n in nodes if n.name not in extra]
    assert [n.name for n in nodes] == [n.name for n in train]
    decode_op, cache_shape = {
        "paged": (OT.OP_PAGED_INC_MULTIHEAD_ATTENTION, (5, 16, 32)),
        "contiguous": (OT.OP_INC_MULTIHEAD_ATTENTION, (2, 33, 32)),
    }[layout]
    assert dec.config.serve_kv_block_size == 16
    attn = 0
    for t, d in zip(train, nodes):
        if t.op_type != OT.OP_MULTIHEAD_ATTENTION:
            assert d.op_type == t.op_type, d.name
            continue
        attn += 1
        assert d.op_type == decode_op, d.name
        assert d.params.front is t.params.front
        assert [tuple(ws.shape) for ws in d.weight_specs
                if not ws.trainable] == [cache_shape] * 2
    assert attn == c.num_layers == 2


# ===================================================================== paged
# The paged-KV matrix (ISSUE 11): token identity with the contiguous
# layout across prompt shapes and slot reuse, COW divergence after a
# shared prefix, refcount-exact reclamation, chunked-prefill interleaving,
# the reserved scratch block, and the layout-keyed warm-start fingerprint.


def test_paged_token_identical_to_contiguous():
    """The full continuous-batching run — ragged prompts, mid-run
    admission, slot reuse — is token-identical between the paged and
    contiguous layouts (the tentpole acceptance criterion)."""
    ff = build_lm(batch=1)
    prompts = PROMPTS + [[2, 4, 6, 8]]
    paged = engine(ff, slots=2, max_new_tokens=6, prefill_chunk=4,
                   kv_layout="paged")
    assert paged.block_manager is not None
    out_paged = paged.generate(prompts)
    contig = engine(ff, slots=2, max_new_tokens=6, prefill_chunk=4,
                    kv_layout="contiguous")
    assert contig.block_manager is None
    assert out_paged == contig.generate(prompts)
    # every completed request released its blocks exactly
    assert paged.block_manager.blocks_in_use == 0
    paged.block_manager.check_invariants()


@pytest.mark.parametrize("layout", ["rectangle", "rows"])
def test_paged_cow_divergence_after_shared_prefix(layout):
    """Two prompts sharing a prefix past block granularity: the second
    admission maps the shared blocks (prefix hit), the first divergent
    write copies exactly the block it lands in (COW), and both token
    streams stay identical to the contiguous engine's. In the rows layout
    the copy lands before a step whose chunk rows all carry the copied
    table row."""
    rows = layout == "rows"
    ff = build_rows_lm() if rows else build_lm(batch=1)
    bs = ROWS["kv_block_size"] if rows else 4
    kw = dict(slots=2, max_new_tokens=5, prefill_chunk=4,
              kv_layout="paged", kv_block_size=bs,
              **({"impl": "flash"} if rows else {}))
    base = [3, 7, 11, 2, 5, 9, 13, 1, 17, 40, 6, 22, 8, 51, 33, 4,
            19, 44, 10, 27, 36, 15, 58, 23, 47, 12, 29, 61, 14, 38, 21, 50]
    # a block and a half shared: one full block + a registered PARTIAL
    # tail; the second prompt extends the prefix INSIDE that partial
    # block, so its first tail write must COW it
    shared = base[:bs + bs // 2]
    prompts = [list(shared), shared + [31, 32]]
    eng = engine(ff, **kw)
    assert eng._chunk_rows == rows
    out = eng.generate(prompts)
    st = eng.block_manager.stats
    assert st.prefix_hits >= 1, "second prompt must share the prefix"
    assert st.shared_tokens >= len(shared)
    assert st.cow_copies >= 1, \
        "divergence inside a shared block must copy-on-write"
    assert eng.stats()["chunk_kernel_steps"] == eng.stats()["row_steps"] == (
        eng.stats()["prefill_calls"] if rows else 0)
    contig = dict(slots=2, max_new_tokens=5, prefill_chunk=4,
                  kv_layout="contiguous")
    assert out == engine(ff, **contig).generate(prompts)

    # identical block-aligned prompts too (the N-users-one-system-prompt
    # case): the whole prompt is shared; only the final token is
    # recomputed and its write COWs the one block it lands in
    aligned = base[:2 * bs]  # 2 full blocks
    eng2 = engine(ff, **kw)     # as new: the prompts above are forgotten
    same = [list(aligned), list(aligned)]
    out2 = eng2.generate(same)
    assert out2[0] == out2[1]
    st2 = eng2.block_manager.stats
    assert st2.shared_tokens >= len(aligned) - 1
    assert st2.cow_copies >= 1
    assert out2 == engine(ff, **contig).generate(same)


def test_paged_refcount_exact_reclamation():
    """Eviction returns exactly the blocks a request held: refcounts hit
    zero in step with completions, shared blocks survive until the LAST
    holder leaves, and the pool drains to empty."""
    from flexflow_tpu.serving.paged import BlockManager

    # pure host-side unit check first (no mesh): see serving/paged.py
    bm = BlockManager(num_blocks=16, block_size=4, table_width=4)
    P1 = list(range(8))
    assert bm.reserve(101, len(P1), 4)
    bm.bind_reservation(101, 0)
    assert bm.admit(0, P1) == 0
    bm.ensure_writable(0, range(8))
    bm.register_prompt(0, P1)
    assert bm.reserve(102, len(P1) + 1, 4)
    bm.bind_reservation(102, 1)
    assert bm.admit(1, P1 + [50]) == 8
    held = bm.blocks_in_use
    bm.release(0)  # shared blocks must survive slot 0's exit
    assert bm.blocks_in_use == held - 0  # slot 0 held only shared blocks
    assert all(bm.refcount(b) == 1 for b in bm._tables[1])
    bm.release(1)
    assert bm.blocks_in_use == 0 and bm.free_blocks == 15
    bm.check_invariants()

    # engine-level: a drained engine's pool is empty, and a second wave
    # reuses the reclaimed blocks without growth
    ff = build_lm(batch=1)
    eng = engine(ff, slots=2, max_new_tokens=4, prefill_chunk=4,
                 kv_layout="paged", kv_block_size=4)
    eng.generate(PROMPTS)
    mgr = eng.block_manager
    assert mgr.blocks_in_use == 0
    peak1 = mgr.stats.blocks_in_use_peak
    eng.generate(PROMPTS)
    assert mgr.blocks_in_use == 0
    assert mgr.stats.blocks_in_use_peak == peak1, \
        "a second identical wave must not grow the working set"
    mgr.check_invariants()


@pytest.mark.parametrize("layout", ["paged", "contiguous", "rows"])
def test_chunked_prefill_interleaves_with_decode(layout):
    """A long prompt's prefill is spread one chunk per iteration, and the
    in-flight decode advances BETWEEN those chunks — without changing its
    token stream (both KV layouts, and the paged one with its chunk steps
    laid out as rows, where `_prefill_calls` still counts a chunk step
    once)."""
    rows = layout == "rows"
    ff = build_rows_lm() if rows else build_lm(batch=1)
    kw = dict(slots=2, max_new_tokens=10, prefill_chunk=4,
              **(ROWS if rows else {"kv_layout": layout}))
    eng = engine(ff, **kw)
    assert eng._chunk_rows == rows
    short = eng.submit(PROMPTS[0])
    # drive until the short request is decoding
    for _ in range(3):
        eng.step()
    s_short = next(s for s in eng.scheduler.slots
                   if s.request is short)
    assert s_short.decoding
    gen_before = len(short.generated)
    long_req = eng.submit(list(range(1, 17)))  # 16 tokens = 4 chunks
    # dispatches the first chunk; fetches the decode step before it
    calls = eng._prefill_calls
    eng.step()
    assert eng._prefill_calls == calls
    progressed = []
    while long_req.first_token_t is None:
        calls = eng._prefill_calls
        eng.step()
        # the count rises in the call that fetches a chunk step
        assert eng._prefill_calls == calls + 1
        progressed.append(len(short.generated))
    # the decode moved during the long prefill, one token per
    # iteration — chunked prefill never stalled the batch
    assert progressed[0] > gen_before
    assert len(progressed) >= 4, "16-token prompt needs >= 4 chunks"
    eng.run_until_drained()
    assert eng.stats()["chunk_kernel_steps"] == eng.stats()["row_steps"] == (
        eng.stats()["prefill_calls"] if rows else 0)

    solo = engine(ff, **kw)
    assert solo.generate([PROMPTS[0]])[0] == short.generated
    assert solo.generate([list(range(1, 17))])[0] == long_req.generated


def test_rows_engine_keeps_the_surface_the_benchmark_calls():
    """benchmarks/jobs/serve.py reaches into the engine: its reference
    check stages a rectangle through `_stage_inputs(tokens, positions)`
    and swaps in a (slots, W) page table of its own, its loop reads
    `_prefill_calls` round every step, its warm-up calls `_apply_copies`.
    On an engine that lays its own chunk steps out as rows those answer as
    on any other."""
    import jax

    from flexflow_tpu.serving.paged import SCRATCH_BLOCK, CopyPlan

    ff = build_rows_lm()
    eng = engine(ff, slots=2, max_new_tokens=2, prefill_chunk=4, **ROWS)
    assert eng._chunk_rows
    slots, W = 2, eng.block_manager.table_width
    for width in (1, 4, 7):
        xs = eng._stage_inputs(
            np.zeros((slots, width), np.int32),
            np.full((slots, width), eng.max_seq_len, np.int32))
        assert xs["page_table"].shape == (slots, W)
        assert xs["tokens"].shape == xs["positions"].shape == (slots, width)
    pools = jax.device_get(eng.decode_model._state)
    for width in (1, 2):
        eng._apply_copies(
            [CopyPlan(src=SCRATCH_BLOCK, dst=SCRATCH_BLOCK)] * width)
    after = jax.device_get(eng.decode_model._state)
    jax.tree.map(np.testing.assert_array_equal, pools, after)
    eng.submit(list(range(1, 10)))      # chunks of 4, 4 and 1, then decode
    seen = []
    while not eng.scheduler.drained:
        before = eng._prefill_calls
        eng.step()
        seen.append(eng._prefill_calls - before)
    # a call fetches the step the call before it dispatched
    assert seen == [0, 1, 1, 1, 0]


def test_paged_scratch_block_guard():
    """The reserved scratch block is the paged equivalent of the
    contiguous scratch ROW (regression for the NaN-poisoning guard):
    position-clipped writes land zeros in block 0 and disturb no live
    block, even when the incoming K/V rows are NaN."""
    import jax.numpy as jnp

    from flexflow_tpu.ops.base import OpContext, get_op_def
    from flexflow_tpu.fftype import OperatorType as OT
    from flexflow_tpu.ops import (
        AttentionFrontEnd, PagedIncMultiHeadAttentionParams,
    )

    E, H, bs, nb, max_seq = 8, 2, 4, 5, 16
    p = PagedIncMultiHeadAttentionParams(
        AttentionFrontEnd(E, H, use_bias=False), max_seq, bs, nb, impl="xla")
    rs = np.random.RandomState(0)
    weights = {w: jnp.asarray(rs.randn(E, E), jnp.float32)
               for w in ("wq", "wk", "wv", "wo")}
    pool_k = jnp.asarray(rs.randn(nb, bs, E), jnp.float32)
    pool_v = jnp.asarray(rs.randn(nb, bs, E), jnp.float32)
    weights["pool_k"], weights["pool_v"] = pool_k, pool_v
    # slot 0 writes position 5 (live, block 1 of its table -> phys 2);
    # slot 1 is clipped to scratch AND carries NaN hidden state (the
    # OOB-position-embedding case the contiguous guard exists for)
    x = jnp.asarray(rs.randn(2, 1, E), jnp.float32)
    x = x.at[1].set(jnp.nan)
    positions = jnp.asarray([[5], [max_seq]], jnp.int32)
    table = jnp.asarray([[1, 2, 3, 4], [0, 0, 0, 0]], jnp.int32)
    fwd = get_op_def(OT.OP_PAGED_INC_MULTIHEAD_ATTENTION).forward
    outs, state = fwd(p, [x, positions, table], weights, None,
                      OpContext(training=False))
    new_k = state["pool_k"]
    # live write: block 2 row 1 (pos 5 = block 1, offset 1) changed
    assert not np.allclose(np.asarray(new_k[2, 1]),
                           np.asarray(pool_k[2, 1]))
    # every OTHER row of every non-scratch block is untouched
    mask = np.ones((nb, bs), bool)
    mask[2, 1] = False
    mask[0, :] = False
    np.testing.assert_array_equal(
        np.asarray(new_k)[mask], np.asarray(pool_k)[mask])
    # the scratch block took the clipped write — as ZEROS, never NaN
    assert np.isfinite(np.asarray(new_k[0])).all()
    assert np.isfinite(np.asarray(state["pool_v"][0])).all()
    # clipped position max_seq-1 = 15 → scratch row 15 % bs = 3
    np.testing.assert_array_equal(
        np.asarray(new_k[0, (max_seq - 1) % bs]), np.zeros((E,)))
    # slot 0's output is finite (slot 1's NaN never crossed rows)
    assert np.isfinite(np.asarray(outs[0][0])).all()


def test_paged_warmstart_layout_fingerprint(tmp_path):
    """--serve-kv-layout round-trips through the warm-start fingerprint:
    each layout's second compile is a cache hit, and the two layouts
    NEVER share a plan address (a paged compile after a contiguous one
    still searches)."""
    ws = str(tmp_path / "ws")
    ff = build_lm(mesh=(2, 4, 1, 1), batch=8,
                  argv=["--only-data-parallel"])
    ov = dict(only_data_parallel=False, search_budget=4,
              enable_parameter_parallel=True,
              enable_attribute_parallel=True, warmstart_dir=ws)
    kw = dict(slots=8, max_new_tokens=4, prefill_chunk=4,
              config_overrides=ov)

    # serve() throughout: each compile's plan source is what is asserted
    paged1 = ff.serve(kv_layout="paged", **kw)
    assert paged1.decode_model._plan_source == "search"
    out1 = paged1.generate(PROMPTS[:2])

    # the contiguous compile must MISS the paged entry (fresh search) ...
    with SearchSpy() as spy:
        contig1 = ff.serve(kv_layout="contiguous", **kw)
    assert contig1.decode_model._plan_source == "search"
    assert spy.searches == 1
    assert contig1.generate(PROMPTS[:2]) == out1

    # ... while each layout's OWN second compile is a zero-eval hit
    with SearchSpy() as spy:
        paged2 = ff.serve(kv_layout="paged", **kw)
        contig2 = ff.serve(kv_layout="contiguous", **kw)
    assert spy.searches == 0 and spy.evals == 0
    assert paged2.decode_model._plan_source == "cache"
    assert contig2.decode_model._plan_source == "cache"
    assert paged2.generate(PROMPTS[:2]) == out1


def test_paged_pool_exhaustion_blocks_admission():
    """A pool too small for two resident requests head-blocks admission
    (FCFS) instead of failing mid-decode: the second request waits for
    the first to release its blocks, and completions stay correct."""
    ff = build_lm(batch=1)
    # 4 blocks + scratch: one request (prompt 5 + 3 new = 2 blocks @ bs=4
    # + COW slack) fits, two do not
    eng = engine(ff, slots=2, max_new_tokens=3, prefill_chunk=4,
                 kv_layout="paged", kv_block_size=4, kv_num_blocks=5)
    r1 = eng.submit(PROMPTS[0])
    r2 = eng.submit(PROMPTS[2])
    eng.step()
    assert eng.scheduler.queue_depth == 1, \
        "pool pressure must keep the second request queued"
    eng.run_until_drained()
    assert r1.finished and r2.finished
    solo = engine(ff, slots=2, max_new_tokens=3, prefill_chunk=4,
                  kv_layout="contiguous")
    assert [r1.generated, r2.generated] == solo.generate(
        [PROMPTS[0], PROMPTS[2]])


def test_paged_analysis_coverage():
    """ffcheck follow-through (ISSUE 11 satellite): the memory-liveness
    pass accounts the pool ONCE per layer (not per slot), the donation
    registry covers the COW copy executable, and the ffsan dtype lattice
    knows the paged op."""
    from flexflow_tpu.analysis import donation, memory
    from flexflow_tpu.analysis.lint import DONATED_CALLEES
    from flexflow_tpu.analysis.numerics import F32_INTERNAL
    from flexflow_tpu.fftype import OperatorType as OT
    from flexflow_tpu.serving import ServingSpec, build_decode_model

    assert OT.OP_PAGED_INC_MULTIHEAD_ATTENTION in F32_INTERNAL
    assert DONATED_CALLEES["_copy_fn"] == (0,)
    table = donation.executor_donation_table()
    assert table["build_block_copy"] == (0,)
    assert not donation.registry_problems()

    ff = build_lm(batch=1)
    c = lm_config()
    bs = 8
    dec4, _ = build_decode_model(ff, ServingSpec(
        slots=4, kv_layout="paged", kv_block_size=bs, kv_num_blocks=9))
    dec8, _ = build_decode_model(ff, ServingSpec(
        slots=8, kv_layout="paged", kv_block_size=bs, kv_num_blocks=9))
    m4 = memory.analyze(dec4.graph, dec4.mesh, training=False)
    m8 = memory.analyze(dec8.graph, dec8.mesh, training=False)
    pool_bytes = c.num_layers * 2 * 9 * bs * c.hidden_size * 4
    # doubling SLOTS must not change the pool's share of weight bytes —
    # the pool is per layer, not per slot (the contiguous cache, by
    # contrast, doubles)
    assert m8["weight_bytes"] == m4["weight_bytes"]
    # and the pool is actually in there: shrinking the pool to the
    # 2-block minimum removes exactly the missing blocks' bytes
    dec_min, _ = build_decode_model(ff, ServingSpec(
        slots=4, kv_layout="paged", kv_block_size=bs, kv_num_blocks=2))
    m_min = memory.analyze(dec_min.graph, dec_min.mesh, training=False)
    assert m4["weight_bytes"] - m_min["weight_bytes"] == \
        pool_bytes - c.num_layers * 2 * 2 * bs * c.hidden_size * 4


@pytest.mark.parametrize("S", [256, 257])
def test_flash_decode_kernel_matches_reference(S):
    """The Pallas single-query decode kernel (interpret mode on CPU)
    matches the einsum reference across partial/full/one-token cache
    fills — also at an odd row count (the engine's cache holds
    max_seq + 1 rows), where the last kv block is ragged."""
    import jax.numpy as jnp

    from flexflow_tpu.kernels.flash_attention import (
        decode_attention_reference,
        flash_decode_attention,
    )

    rs = np.random.RandomState(0)
    slots, H, hd = 3, 2, 64
    E = H * hd
    q = jnp.asarray(rs.randn(slots, 1, E), jnp.float32)
    k = jnp.asarray(rs.randn(slots, S, E), jnp.float32)
    v = jnp.asarray(rs.randn(slots, S, E), jnp.float32)
    lengths = jnp.asarray([1, 100, S], jnp.int32)
    ref = decode_attention_reference(q, k, v, (lengths - 1)[:, None],
                                     num_heads=H)
    out = flash_decode_attention(q, k, v, lengths, num_heads=H,
                                 block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# tolerance by dtype, set from the mantissa: the kernel normalises after
# the p·V contraction and the oracle before it, so the two round p to the
# pool's dtype at different magnitudes (bf16 keeps 8 bits)
_PAGED_PARITY_TOL = {"float32": 2e-5, "bfloat16": 2e-2}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("H,hd,bs,W", [
    (2, 64, 16, 16),    # 256 rows: exactly two rounds of 8 pages
    (4, 128, 16, 20),   # 320 rows: the last round has 4 of its 8 pages
    (4, 128, 128, 3),   # one page a round
    (3, 128, 32, 5),    # 160 rows, heads not a multiple of the sublanes
], ids=["hd64-bs16", "hd128-bs16-ragged", "hd128-bs128", "hd128-bs32-3heads"])
def test_paged_flash_decode_kernel_matches_reference(H, hd, bs, W, dtype):
    """The PAGED Pallas decode kernel — its body walking each slot's page
    table, a round of ~128 rows a DMA, all heads in one pass — matches the
    gather + einsum oracle: lengths 0, 1, one round, one round + 1, the
    full table and two partial fills; scrambled tables; a slot sharing
    another's blocks; and every table entry past a slot's cursor pointing
    at a block of NaN, as do the rows past it inside its last block
    (masked rows never reach the output)."""
    import jax.numpy as jnp

    from flexflow_tpu.kernels.flash_attention import (
        paged_decode_attention_reference,
        paged_flash_decode_attention,
    )

    rs = np.random.RandomState(0)
    E = H * hd
    full = W * bs
    lengths = np.asarray([0, 1, 128, 129, full, 100, 77], np.int32)
    slots = len(lengths)
    own = slots - 1  # the last slot SHARES the full slot's blocks
    nan_block = 0
    nb = 1 + own * W
    pool_k = rs.randn(nb, bs, E).astype(np.float32)
    pool_v = rs.randn(nb, bs, E).astype(np.float32)
    pool_k[nan_block] = pool_v[nan_block] = np.nan
    table = np.full((slots, W), nan_block, np.int32)
    for s in range(own):
        used = -(-int(lengths[s]) // bs)
        blocks = rs.permutation(np.arange(1 + s * W, 1 + (s + 1) * W))
        table[s, :used] = blocks[:used]
        if lengths[s] % bs:  # stale rows past the cursor in the last block
            pool_k[blocks[used - 1], lengths[s] % bs:] = np.nan
            pool_v[blocks[used - 1], lengths[s] % bs:] = np.nan
    table[own] = table[4]  # prefix reuse: 77 rows of the full slot's 320
    dt = jnp.dtype(dtype)
    q = jnp.asarray(rs.randn(slots, 1, E), dt)
    table, lengths = jnp.asarray(table), jnp.asarray(lengths)
    # the oracle's 0·NaN is NaN, so it reads the same pool with the NaN
    # (all in masked rows) cleaned out
    ref = paged_decode_attention_reference(
        q, jnp.asarray(np.nan_to_num(pool_k), dt),
        jnp.asarray(np.nan_to_num(pool_v), dt), table,
        (lengths - 1)[:, None], num_heads=H)
    out = paged_flash_decode_attention(
        q, jnp.asarray(pool_k, dt), jnp.asarray(pool_v, dt), table, lengths,
        num_heads=H)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    assert np.isfinite(out).all()  # the empty slot's row too
    tol = _PAGED_PARITY_TOL[dtype]
    # slot 0 is empty: the oracle's softmax over no keys is uniform, the
    # kernel's is zero, and neither is ever consumed
    np.testing.assert_allclose(out[1:], ref[1:], rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("start", [0, 384])
def test_paged_flash_decode_kernel_rows_outnumber_slots(start, dtype):
    """A step that carries a prefill chunk as rows (serving/engine.py):
    16 slot rows, then the 128 rows of a chunk's bucket, all carrying ONE
    slot's page-table row. Chunk row i attends `start + i + 1` keys, the
    chunk's own earlier rows among them; the bucket's tail past the
    chunk's 100 tokens is dead (length 0), as are some of the slots
    (empty ones, and the prefilling slot's own row). Rows past a cursor
    hold NaN, as do the blocks no live row maps."""
    import jax.numpy as jnp

    from flexflow_tpu.kernels.flash_attention import (
        paged_decode_attention_reference,
        paged_flash_decode_attention,
    )

    rs = np.random.RandomState(start)
    H, hd, bs, W = 2, 128, 16, 32
    E, slots, bucket, n = H * hd, 16, 128, 100
    slot_len = rs.randint(1, W * bs + 1, slots).astype(np.int32)
    slot_len[[2, 7, 11]] = 0            # empty slots, the prefilling slot
    chunk_len = np.where(np.arange(bucket) < n,
                         start + 1 + np.arange(bucket), 0).astype(np.int32)
    lengths = np.concatenate([slot_len, chunk_len])
    nb = 1 + slots * W
    pool_k = rs.randn(nb, bs, E).astype(np.float32)
    pool_v = rs.randn(nb, bs, E).astype(np.float32)
    pool_k[0] = pool_v[0] = np.nan      # scratch stands for "unmapped"
    table = np.zeros((slots + bucket, W), np.int32)
    for s in range(slots):
        used = -(-int(max(slot_len[s], (start + n) * (s == 7))) // bs)
        table[s, :used] = rs.permutation(
            np.arange(1 + s * W, 1 + (s + 1) * W))[:used]
    table[slots:] = table[7]            # the chunk is slot 7's
    last = table[7, (start + n - 1) // bs]
    pool_k[last, (start + n) % bs or bs:] = np.nan
    pool_v[last, (start + n) % bs or bs:] = np.nan
    dt = jnp.dtype(dtype)
    q = jnp.asarray(rs.randn(slots + bucket, 1, E), dt)
    table, lengths = jnp.asarray(table), jnp.asarray(lengths)
    ref = paged_decode_attention_reference(
        q, jnp.asarray(np.nan_to_num(pool_k), dt),
        jnp.asarray(np.nan_to_num(pool_v), dt), table,
        (lengths - 1)[:, None], num_heads=H)
    out = paged_flash_decode_attention(
        q, jnp.asarray(pool_k, dt), jnp.asarray(pool_v, dt), table, lengths,
        num_heads=H)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    assert np.isfinite(out).all()       # the dead rows too
    live = np.asarray(lengths) > 0
    assert live.sum() == slots - 3 + n
    tol = _PAGED_PARITY_TOL[dtype]
    np.testing.assert_allclose(out[live], ref[live], rtol=tol, atol=tol)
