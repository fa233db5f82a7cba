"""A serving step samples from the rows it reads (PR 56): the decode
step cuts the graph at its row-wise tail (the final norm and the
vocabulary head, `Executor.decode_tail`) and runs the tail and the sampler
on the slots' rows and a chunk's last live row, in each of the step's
three layouts (docs/serving.md), against `_apply`'s logits of every row,
which the benchmark's jobs keep calling for.
"""

import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import small_lms

SLOTS, MAX_SEQ, VOCAB = 3, 32, 64
SERVE = dict(slots=SLOTS, max_seq_len=MAX_SEQ, prefill_chunk=8,
             kv_layout="paged", kv_block_size=4)
PROMPT = [3, 7, 11, 2, 5, 9, 4, 1, 30, 12, 8]


def _engine(head: str):
    """A two-layer LM's serving engine: `tied` / `untied` head, or an LM
    whose logits are `scaled` by an op that reads two values and so ends
    the graph with no row-wise tail."""
    sys.argv = ["test", "--seed", "11"]
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models import TransformerLMConfig, build_transformer_lm

    cfg = FFConfig()
    cfg.mesh_axis_sizes = (1, 1, 1, 1)
    cfg.batch_size = 2
    ff = FFModel(cfg)
    _, logits = build_transformer_lm(ff, TransformerLMConfig(
        vocab_size=VOCAB, hidden_size=32, num_heads=4, num_layers=2,
        sequence_length=MAX_SEQ, attention_impl="xla",
        tie_embeddings=head == "tied"), batch_size=2)
    if head == "scaled":
        ff.add(logits, logits, name="doubled")
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    return ff.serve(**SERVE)


@pytest.fixture(scope="module", params=["tied", "untied"])
def engine(request):
    return _engine(request.param)


def _staged(eng, tokens, positions, row_slots=None):
    """The call's inputs as the engine stages them, slot i on its own run
    of pool blocks (block 0 is scratch)."""
    xs = eng._stage_inputs(tokens, positions, row_slots)
    width = eng.block_manager.table_width
    table = 1 + np.arange(SLOTS * width, dtype=np.int32).reshape(SLOTS, width)
    xs["page_table"] = jax.device_put(
        table if row_slots is None else table[row_slots],
        xs["page_table"].sharding)
    return xs


def _logits(eng, xs):
    """Every row's logits, as the benchmark's jobs ask `_apply` for them;
    the cache is left as it was."""
    dec, ex = eng.decode_model, eng.decode_model.executor
    logits, _, _ = jax.jit(lambda p, s, x: ex._apply(
        p, s, ex._cast_compute(x), training=False, rng=None))(
            dec._params, dec._state, xs)
    return np.asarray(logits, np.float32)


def _step(eng, xs, read_idx, temperature=None):
    dec = eng.decode_model
    rows = read_idx.shape[0]
    dec._state, sampled = eng._step_fn(
        dec._params, dec._state, xs, jnp.asarray(read_idx, jnp.int32),
        jax.random.key(0),
        jnp.zeros((rows,), jnp.float32) if temperature is None
        else jnp.asarray(temperature, jnp.float32))
    return np.asarray(sampled)


def _layout(kind: str, n: int = 0, bucket: int = 0):
    """(tokens, positions, row_slots, read_idx, the rows sampled from as
    (row, column of the logits)) of a call in which slot 0 decodes at
    position 4 and, for a chunk, slot 1 prefills `n` tokens of PROMPT in
    a bucket of `bucket`."""
    rows, q = {"decode": (SLOTS, 1), "rectangle": (SLOTS, bucket),
               "rows": (SLOTS + bucket, 1)}[kind]
    tokens = np.zeros((rows, q), np.int32)
    positions = np.full((rows, q), MAX_SEQ, np.int32)
    read_idx = np.zeros((rows,), np.int32)
    tokens[0, 0], positions[0, 0] = 17, 4
    read = [(s, 0) for s in range(SLOTS)]
    row_slots = None
    if kind == "rectangle":
        tokens[1, :n], positions[1, :n] = PROMPT[:n], np.arange(n)
        read_idx[1] = n - 1
        read[1] = (1, n - 1)
    elif kind == "rows":
        tokens[SLOTS:SLOTS + n, 0] = PROMPT[:n]
        positions[SLOTS:SLOTS + n, 0] = np.arange(n)
        row_slots = np.r_[np.arange(SLOTS), np.full((bucket,), 1)]
        read.append((SLOTS + n - 1, 0))
    return tokens, positions, row_slots, read_idx, read


LAYOUTS = {
    "decode": ("decode", 0, 0),
    "rectangle-3-of-4": ("rectangle", 3, 4),
    "rectangle-8-of-8": ("rectangle", 8, 8),
    "rows-3-of-4": ("rows", 3, 4),
    "rows-5-of-8": ("rows", 5, 8),
    "rows-8-of-8": ("rows", 8, 8),
}


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_sampled_is_the_argmax_of_the_rows_read(engine, layout):
    """Greedy rows: the step's token at the slots' rows and at the
    chunk's last live row is the arg-max of `_apply`'s logits there,
    `read_idx` of zeros for a chunk as rows as the benchmark's jobs pass
    it; every other row of a chunk as rows reads 0."""
    tokens, positions, row_slots, read_idx, read = _layout(*LAYOUTS[layout])
    xs = _staged(engine, tokens, positions, row_slots)
    logits = _logits(engine, xs)
    assert logits.shape == tokens.shape + (VOCAB,)
    sampled = _step(engine, xs, read_idx)
    assert sampled.shape == (tokens.shape[0],) and sampled.dtype == np.int32
    for row, col in read:
        assert sampled[row] == np.argmax(logits[row, col]), (row, col)
    others = sorted(set(range(tokens.shape[0])) - {row for row, _ in read})
    assert not sampled[others].any()


@pytest.mark.parametrize("layout", ["decode", "rectangle-3-of-4",
                                    "rows-5-of-8"])
def test_a_warm_row_draws_from_its_own_logits(engine, layout):
    """At a temperature above zero a row's token is the arg-max of its
    logits over the temperature plus Gumbel noise of the rows sampled,
    under the step's key: the temperatures are gathered as the rows
    are."""
    tokens, positions, row_slots, read_idx, read = _layout(*LAYOUTS[layout])
    xs = _staged(engine, tokens, positions, row_slots)
    logits = _logits(engine, xs)
    temperature = np.zeros((tokens.shape[0],), np.float32)
    warm = [read[0][0], read[-1][0]]
    temperature[warm] = 0.7, 1.3
    sampled = _step(engine, xs, read_idx, temperature)
    noise = np.asarray(jax.random.gumbel(
        jax.random.key(0), (len(read), VOCAB), jnp.float32))
    for i, (row, col) in enumerate(read):
        want = logits[row, col]
        if temperature[row] > 0:
            want = want / temperature[row] + noise[i]
        assert sampled[row] == np.argmax(want), (row, col)


def _lowered(eng, layout: str, step=None) -> str:
    tokens, positions, row_slots, read_idx, _ = _layout(*LAYOUTS[layout])
    dec = eng.decode_model
    return (step or eng._step_fn).lower(
        dec._params, dec._state, _staged(eng, tokens, positions, row_slots),
        jnp.asarray(read_idx), jax.random.key(0),
        jnp.zeros((tokens.shape[0],), jnp.float32)).as_text()


def _vocab_wide(text: str) -> set:
    """The leading dimensions of every tensor type in a lowered module
    whose last dimension is the vocabulary, the head's kernel left out."""
    found = re.findall(rf"tensor<((?:\d+x)+){VOCAB}x[a-z]", text)
    return {tuple(int(d) for d in dims.split("x") if d)
            for dims in found} - {(32,)}


@pytest.mark.parametrize("layout,head_rows", [
    ("rows-5-of-8", SLOTS + 1), ("rows-3-of-4", SLOTS + 1),
    ("rectangle-3-of-4", SLOTS), ("decode", SLOTS)])
def test_the_head_and_the_argmax_run_on_the_rows_read(engine, layout,
                                                      head_rows):
    """Structure of the lowered step: the head's dot and the arg-max
    take `head_rows` rows and no tensor of the program has the vocabulary
    at more; a step that only decodes is the program it was before the
    cut, to the letter: nothing is gathered in front of its tail."""
    text = _lowered(engine, layout)
    assert re.search(rf"stablehlo.dot_general .*-> "
                     rf"tensor<{head_rows}x1x{VOCAB}xf32>", text)
    assert re.search(rf"call @argmax.*\(tensor<{head_rows}x{VOCAB}xf32>\) "
                     rf"-> tensor<{head_rows}xi32>", text)
    assert _vocab_wide(text) == {(head_rows, 1), (head_rows,)}
    if layout == "decode":
        assert text == _lowered(engine, layout, _the_step_before(engine))


def _the_step_before(eng):
    """The step as it was before the cut: every row's logits, then the
    rows read."""
    ex = eng.decode_model.executor

    def decode_step(params, state, x_inputs, read_idx, rng, temperature):
        logits, new_state, _ = ex._apply(
            params, state, ex._cast_compute(x_inputs), training=False,
            rng=None)
        sel = logits[jnp.arange(logits.shape[0]), read_idx]
        sel = sel.astype(jnp.float32)
        t = temperature.astype(jnp.float32)[:, None]
        gumbel = jax.random.gumbel(rng, sel.shape, jnp.float32)
        noisy = jnp.where(t > 0.0, sel / jnp.maximum(t, 1e-6) + gumbel, sel)
        return (ex._pin_at_rest(ex._restore_state_dtypes(new_state)),
                jnp.argmax(noisy, axis=-1).astype(jnp.int32))

    return jax.jit(decode_step)


def test_a_graph_without_a_row_wise_tail_samples_from_its_output():
    """The last op reads two values: the cut is at the output, every
    row's logits are computed and the sampler alone runs on the rows
    read."""
    eng = _engine("scaled")
    ex = eng.decode_model.executor
    tail, cut = ex.decode_tail()
    assert tail == () and cut == (ex.logits_node.guid, 0)
    for layout in ("rows-5-of-8", "rectangle-3-of-4", "decode"):
        tokens, positions, row_slots, read_idx, read = _layout(
            *LAYOUTS[layout])
        xs = _staged(eng, tokens, positions, row_slots)
        logits = _logits(eng, xs)
        sampled = _step(eng, xs, read_idx)
        for row, col in read:
            assert sampled[row] == np.argmax(logits[row, col])
        assert eng._head_rows_of(*tokens.shape) == tokens.size


def test_the_tail_of_an_lm_is_its_final_norm_and_head(engine):
    ex = engine.decode_model.executor
    tail, (guid, out) = ex.decode_tail()
    assert [n.name for n in tail] == ["ln_f", "lm_head"]
    assert ex.graph.nodes[guid].name == "l1_res2" and out == 0
    assert ex.decode_context.slots == SLOTS
    assert ex.decode_context.max_seq == MAX_SEQ


@pytest.mark.parametrize("op,params,shape,want", [
    ("OP_LINEAR", dict(out_channels=8), (4, 1, 16), True),
    ("OP_RMSNORM", dict(), (4, 1, 16), True),
    ("OP_LAYERNORM", dict(axes=(-1,)), (4, 1, 16), True),
    ("OP_LAYERNORM", dict(axes=(2,)), (4, 1, 16), True),
    ("OP_LAYERNORM", dict(axes=(1, 2)), (4, 1, 16), False),
    ("OP_CAST", None, (4, 1, 16), True),
    ("OP_GELU", None, (4, 1, 16), True),
    ("OP_SCALAR_MULTIPLY", None, (4, 1, 16), True),
    ("OP_DROPOUT", None, (4, 1, 16), True),
    ("OP_SOFTMAX", None, (4, 1, 16), False),
    ("OP_EW_ADD", None, (4, 1, 16), False),
    ("OP_PAGED_INC_MULTIHEAD_ATTENTION", None, (4, 1, 16), False),
    ("OP_EMBEDDING", None, (4, 1), False),
])
def test_ops_declare_whether_they_act_row_by_row(op, params, shape, want):
    from flexflow_tpu.fftype import OperatorType
    from flexflow_tpu.ops import core
    from flexflow_tpu.ops.base import get_op_def

    made = {"OP_LINEAR": core.LinearParams, "OP_RMSNORM": core.RMSNormParams,
            "OP_LAYERNORM": core.LayerNormParams}
    p = made[op](**params) if params is not None else None
    assert get_op_def(OperatorType[op]).row_wise(p, [shape]) is want


def test_a_row_count_the_data_axis_does_not_divide_stays_whole():
    """The tail's outputs are pinned to the plan's placement of the
    declared rows; `slots + 1` rows that the axes of dim 0 do not divide
    keep the placement of their other dimensions only."""
    from types import SimpleNamespace

    from jax.sharding import Mesh, PartitionSpec as P

    from flexflow_tpu.executor import Executor

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    ex = SimpleNamespace(mesh=mesh)

    def placed(rows):
        return jax.jit(lambda x: Executor._placed(
            ex, x, P("data", None, "model"), any_rows=True))(
                jnp.zeros((rows, 1, 8))).sharding.spec

    assert placed(4) == P("data", None, "model")
    assert placed(5) == P(None, None, "model")


def test_apply_as_the_benchmark_calls_it_returns_every_row(engine):
    tokens, positions, row_slots, _, _ = _layout("rows", 5, 8)
    logits = _logits(engine, _staged(engine, tokens, positions, row_slots))
    assert logits.shape == (SLOTS + 8, 1, VOCAB)
    tokens, positions, _, _, _ = _layout("rectangle", 3, 4)
    logits = _logits(engine, _staged(engine, tokens, positions))
    assert logits.shape == (SLOTS, 4, VOCAB)
    # and the tail of some rows is those rows of the whole
    ex, dec = engine.decode_model.executor, engine.decode_model
    xs = _staged(engine, tokens, positions)
    hidden, _, _ = ex._apply(dec._params, dec._state, ex._cast_compute(xs),
                             training=False, rng=None, upto_tail=True)
    assert hidden.shape == (SLOTS, 4, 32)
    np.testing.assert_allclose(
        np.asarray(ex._apply_tail(dec._params, hidden[1:2, 2:3])),
        logits[1:2, 2:3], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layout", ["rectangle", "rows"])
def test_stats_count_the_rows_through_the_head(layout, monkeypatch):
    """A closed loop of three requests: `step_rows` sums the rows of the
    dispatched steps, `head_rows` the rows that went through the tail,
    and the step's span carries its own."""
    if layout == "rows":
        eng = small_lms.engine(small_lms.build_rows_lm(), slots=2,
                               prefill_chunk=8, **small_lms.ROWS)
    else:
        eng = _engine("untied")
    slots = eng.spec.slots
    assert eng._chunk_rows == (layout == "rows")
    shapes, schedule = [], eng._schedule

    def spy():
        step = schedule()
        if step is not None:
            shapes.append((step.tokens.shape, step.span[1]["head_rows"]))
        return step

    monkeypatch.setattr(eng, "_schedule", spy)
    got = eng.generate([PROMPT, PROMPT[:3], PROMPT[2:9]], max_new_tokens=4)
    assert all(len(g) == 4 for g in got)
    stats = eng.stats()
    assert stats["iterations"] == len(shapes)
    assert stats["step_rows"] == sum(r * q for (r, q), _ in shapes)
    assert stats["head_rows"] == sum(h for _, h in shapes)
    for (rows, q), head in shapes:
        assert head == slots + (rows > slots)
    assert stats["head_rows"] < stats["step_rows"]
    assert any(rows > slots for (rows, _), _ in shapes) == (layout == "rows")
    eng.reset_stats()
    assert eng.stats()["head_rows"] == eng.stats()["step_rows"] == 0
