"""A decode model's tensors rest in the compute dtype (docs/serving.md).

Under --dtype bf16 the decode graph declares its KV cache bf16, its
executor (an inference compile) holds its parameters bf16, and
`adopt_params` casts each trained weight once on the way in. Two things
are held here:

  - equivalence: the steps compute bitwise what the cast-at-use
    formulation computes: fp32 masters cast to bf16 at first use in
    every step, an fp32 cache written with bf16 values and cast back
    before attention. The program has no switch for that formulation:
    `_cast_at_use` below builds its inputs (fp32 copies of the masters,
    an fp32 cache) and feeds them to the same `executor._apply`;
  - stability: every step of the serving surface leaves every state leaf
    in its declared dtype, so no step's executable is traced twice.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.serving.decode_graph import KV_LEAVES
from small_lms import (
    PROMPTS, ROWS, ROWS_SEQ, build_lm, engine, new_lm, staged_shapes,
)

BF16 = ["--dtype", "bf16"]
CASES = {  # the trained model's sequence length, serve()'s options
    "paged": (32, dict(kv_layout="paged")),
    "contiguous": (32, dict(kv_layout="contiguous")),
    # impl="flash": single-query calls go through the paged kernel (the
    # interpreter runs it here), so a chunk can ride as rows; a table of
    # four pages, since the two formulations lower every bucket twice and
    # the interpreter's lowering is paid by the page
    "paged-kernel": (ROWS_SEQ, {**ROWS, "kv_block_size": 32}),
}


def _lm(case, argv=BF16, build=build_lm):
    sequence_length, serve_kw = CASES[case]
    ff = build(batch=1, argv=argv, sequence_length=sequence_length)
    return ff, dict(slots=2, max_new_tokens=6, prefill_chunk=4, **serve_kw)


def _cast_at_use(ff, kw):
    """An engine of its own, turned into the parent's formulation from
    outside: its decode model holds fp32 copies of the trained masters and
    an fp32 cache, and its executor declares every leaf fp32, so each step
    casts the weights at first use and hands the cache back as fp32."""
    eng = ff.serve(**kw)
    dec = eng.decode_model
    ex = dec.executor
    ex.rest_dtypes = {k: jnp.float32 if jnp.issubdtype(v, jnp.floating)
                      else v for k, v in ex.rest_dtypes.items()}
    dec._params = {
        node: {w: jnp.array(ff._params[ff._resolve_weight_owner(node)][w])
               for w in ws}
        for node, ws in dec._params.items()}
    dec._state = {node: {w: jnp.zeros(x.shape, jnp.float32)
                         for w, x in ws.items()}
                  for node, ws in dec._state.items()}
    eng._step_fn = ex.build_decode_step()
    return eng


def _leaf_dtypes(tree):
    return {str(x.dtype) for ws in tree.values() for x in ws.values()
            if jnp.issubdtype(x.dtype, jnp.floating)}


def _call(eng, tokens, positions, row_slots=None):
    """One call of the decode graph through `executor._apply`, as the
    engine's step makes it; float32 copies of the logits, the state
    carried in `decode_model._state`. Slot i owns its own run of pool
    blocks (block 0 is scratch)."""
    dec = eng.decode_model
    ex = dec.executor
    xs = eng._stage_inputs(tokens, positions, row_slots)
    if "page_table" in xs:
        width = eng.block_manager.table_width
        table = 1 + np.arange(eng.spec.slots * width, dtype=np.int32
                              ).reshape(eng.spec.slots, width)
        xs["page_table"] = jax.device_put(
            table if row_slots is None else table[row_slots],
            xs["page_table"].sharding)

    def apply(params, state, xs):
        logits, new_state, _ = ex._apply(
            params, state, ex._cast_compute(xs), training=False, rng=None)
        return logits, ex._restore_state_dtypes(new_state)

    logits, dec._state = jax.jit(apply)(dec._params, dec._state, xs)
    assert logits.dtype == jnp.bfloat16
    return np.asarray(logits.astype(jnp.float32))


def _cache(eng):
    return {(node, w): np.asarray(x.astype(jnp.float32))
            for node, ws in eng.decode_model._state.items()
            for w, x in ws.items() if w in KV_LEAVES}


@pytest.mark.parametrize("case", list(CASES))
def test_steps_are_bitwise_the_cast_at_use_formulation(case):
    """A chunk step as a rectangle, a decode step and (where the kernel
    serves single-query rows) a chunk step as rows: the same logits to
    the bit, and the same cache contents, as fp32 masters cast at use
    over an fp32 cache."""
    ff, kw = _lm(case)
    # serve(): the whole cache is compared below, from all zeros on
    rest, at_use = ff.serve(**kw), _cast_at_use(ff, kw)
    assert _leaf_dtypes(rest.decode_model._params) == {"bfloat16"}
    assert _leaf_dtypes(rest.decode_model._state) == {"bfloat16"}
    assert _leaf_dtypes(at_use.decode_model._params) == {"float32"}
    slots, scratch = rest.spec.slots, rest.max_seq_len
    prompt = PROMPTS[2]

    calls = []
    # slot 0 prefills four tokens as one rectangular chunk
    tokens = np.zeros((slots, 4), np.int32)
    positions = np.full((slots, 4), scratch, np.int32)
    tokens[0], positions[0] = prompt[:4], np.arange(4)
    calls.append((tokens, positions, None))
    # slot 0 decodes one token
    tokens = np.zeros((slots, 1), np.int32)
    positions = np.full((slots, 1), scratch, np.int32)
    tokens[0, 0], positions[0, 0] = prompt[4], 4
    calls.append((tokens, positions, None))
    if rest._chunk_rows:
        # slot 0 decodes another while slot 1's chunk rides as four rows
        tokens = np.zeros((slots + 4, 1), np.int32)
        positions = np.full((slots + 4, 1), scratch, np.int32)
        tokens[0, 0], positions[0, 0] = prompt[5], 5
        tokens[slots:, 0], positions[slots:, 0] = PROMPTS[0][:4], np.arange(4)
        calls.append((tokens, positions,
                      np.r_[np.arange(slots), np.full((4,), 1)]))
    else:
        assert case != "paged-kernel"

    for tokens, positions, row_slots in calls:
        got = _call(rest, tokens, positions, row_slots)
        want = _call(at_use, tokens, positions, row_slots)
        np.testing.assert_array_equal(got, want)
        assert _leaf_dtypes(rest.decode_model._state) == {"bfloat16"}
        assert _leaf_dtypes(at_use.decode_model._state) == {"float32"}
        a, b = _cache(rest), _cache(at_use)
        assert a.keys() == b.keys() and a
        for key in a:
            np.testing.assert_array_equal(a[key], b[key])
    assert any(np.any(x) for x in _cache(rest).values())


@pytest.mark.parametrize("case", list(CASES))
def test_generate_gives_the_cast_at_use_tokens(case):
    ff, kw = _lm(case)
    rest, at_use = engine(ff, **kw), _cast_at_use(ff, kw)
    got = rest.generate(PROMPTS)
    assert got == at_use.generate(PROMPTS)
    assert all(len(g) == 6 for g in got)
    assert (rest.stats()["row_steps"] > 0) == (case == "paged-kernel")
    assert _leaf_dtypes(at_use.decode_model._state) == {"float32"}


@pytest.mark.parametrize("argv,want", [(BF16, "bfloat16"), ([], "float32")],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_decode_model_rests_in_the_compute_dtype(layout, argv, want):
    """Parameters and cache of the decode model: bf16 under --dtype bf16,
    fp32 without it; the trained model's masters are fp32 either way and
    the decode model's copies are its own."""
    ff, kw = _lm(layout, argv)
    eng = engine(ff, **kw)
    dec = eng.decode_model
    assert _leaf_dtypes(dec._params) == {want}
    assert _leaf_dtypes(dec._state) == {want}
    assert _leaf_dtypes(ff._params) == {"float32"}
    for node, ws in dec._params.items():
        for w, x in ws.items():
            src = ff._params[ff._resolve_weight_owner(node)][w]
            assert (x.unsafe_buffer_pointer()
                    != src.unsafe_buffer_pointer())
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(src.astype(x.dtype)))
    itemsize = np.dtype(want).itemsize
    assert eng._kv_itemsize == itemsize


def test_serve_compile_event_says_what_rests_where(tmp_path):
    from flexflow_tpu.telemetry import read_jsonl

    for argv, itemsize in ((BF16, 2), ([], 4)):
        # a model of its own: the session is the model's
        ff, kw = _lm("paged", argv, build=new_lm)
        ff.enable_telemetry(str(tmp_path / f"tel{itemsize}"))
        eng = ff.serve(**kw)
        eng.telemetry.close()
        (ev,) = [r for r in read_jsonl(
            str(tmp_path / f"tel{itemsize}" / "metrics.jsonl"))
            if r["kind"] == "serve.compile"]
        dec = eng.decode_model
        n_params = sum(x.size for ws in dec._params.values()
                       for x in ws.values())
        n_kv = sum(x.size for ws in dec._state.values()
                   for w, x in ws.items() if w in KV_LEAVES)
        assert ev["kv_stored_itemsize"] == itemsize
        assert ev["weight_bytes_at_rest"] == n_params * itemsize
        assert ev["kv_bytes_at_rest"] == n_kv * itemsize > 0
        assert (eng.kv_bytes_per_layer() * len(eng.kv_pool_layers())
                == ev["kv_bytes_at_rest"])


def _declared(dec):
    return {(node, w): np.dtype(dec.executor.rest_dtypes[(node, w)])
            for node, ws in dec._state.items() for w in ws}


def _assert_state_as_declared(dec):
    declared = _declared(dec)
    assert declared
    for node, ws in dec._state.items():
        for w, x in ws.items():
            assert x.dtype == declared[(node, w)], (node, w)
            if w in KV_LEAVES:
                assert x.dtype == jnp.bfloat16


@pytest.mark.parametrize("case", ["paged", "contiguous", "paged-kernel"])
def test_state_keeps_its_declared_dtype_and_no_step_retraces(
        case, monkeypatch):
    """Decode steps, chunk steps (rectangles and rows), block copies and
    a KV inject: every state leaf is left in its declared dtype and the
    step holds one executable per staged shape."""
    from flexflow_tpu.serving.paged import SCRATCH_BLOCK, CopyPlan

    ff, kw = _lm(case)
    eng = ff.serve(**kw)    # its own: its executables are counted from none
    dec = eng.decode_model
    shapes = staged_shapes(eng, monkeypatch)
    _assert_state_as_declared(dec)
    eng.generate(PROMPTS)
    _assert_state_as_declared(dec)
    if eng.block_manager is not None:
        eng._apply_copies([CopyPlan(src=SCRATCH_BLOCK, dst=SCRATCH_BLOCK)])
        _assert_state_as_declared(dec)
        # rows handed over in fp32 land in the pool's dtype
        layers, mgr = eng.kv_pool_layers(), eng.block_manager
        embed = dec._state[layers[0]]["pool_k"].shape[-1]
        rows = np.ones((len(layers), 1, mgr.block_size, embed), np.float32)
        eng._inject_rows([mgr.num_blocks - 1], rows, 2 * rows)
        _assert_state_as_declared(dec)
        pool_v = dec._state[layers[0]]["pool_v"]
        assert float(pool_v[mgr.num_blocks - 1, 0, 0]) == 2.0
        assert eng._copy_fn._cache_size() >= 1
        assert eng._inject_fn._cache_size() == 1
    eng.generate([p + [5] for p in PROMPTS])
    _assert_state_as_declared(dec)
    assert eng._step_fn._cache_size() == len(set(shapes))


def test_verify_step_keeps_declared_dtypes():
    """Speculative rounds under bf16: target and drafter both rest in
    bf16 after verify steps, the verify step holds one executable a
    draft length, and the streams are the plain engine's."""
    from test_speculative import _force_speculation

    ff, kw = _lm("paged")
    base = engine(ff, **kw).generate(PROMPTS)
    dff, _ = _lm("paged", build=new_lm)
    # serve(): a speculative engine holds a drafter's engine of its own
    eng = ff.serve(speculate=True, draft_model=dff, **kw)
    _force_speculation(eng)
    assert eng.generate(PROMPTS) == base
    assert eng.stats()["speculation"]["rounds"] > 1
    _assert_state_as_declared(eng.decode_model)
    assert _leaf_dtypes(eng.decode_model._params) == {"bfloat16"}
    sizes = eng._verify_fn._cache_size()
    eng.generate([p + [5] for p in PROMPTS])
    _assert_state_as_declared(eng.decode_model)
    assert eng._verify_fn._cache_size() == sizes


def test_batchnorm_trained_under_bf16_keeps_fp32_statistics(rng):
    """A training compile declares nothing but fp32: masters and running
    statistics stay fp32 under --dtype bf16, and the train step is traced
    once."""
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.fftype import DataType

    config = FFConfig()
    config.batch_size = 8
    config.computation_dtype = DataType.DT_BFLOAT16
    ff = FFModel(config)
    x = ff.create_tensor((8, 3, 8, 8))
    t = ff.batch_norm(ff.conv2d(x, 4, 3, 3, 1, 1, 1, 1))
    ff.softmax(ff.dense(ff.flat(t), 4))
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    assert set(map(np.dtype, ff.executor.rest_dtypes.values())) == {
        np.dtype("float32")}
    before = {k: np.asarray(v) for ws in ff._state.values()
              for k, v in ws.items()}
    ff.fit(rng.randn(16, 3, 8, 8).astype(np.float32),
           rng.randint(0, 4, (16, 1)).astype(np.int32), epochs=2)
    assert _leaf_dtypes(ff._state) == {"float32"}
    assert _leaf_dtypes(ff._params) == {"float32"}
    assert any(np.any(np.asarray(v) != before[k])
               for ws in ff._state.values() for k, v in ws.items())
    assert ff.executor._train_step._cache_size() == 1
