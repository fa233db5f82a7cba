"""A serving step crosses to the device once (PR 54): one packed put and
one program, `feed`, in front of the step, against the plain staging they
replaced, which stays as the oracle: `_stage_inputs` (an array a put), the
select of the sampled tokens, the host's `read_idx` and temperatures, the
key split on the host's side.

One plain LM serves every case. Which feeds a step has is read from facts
of the built engine (the block manager's window group, the bytes of
recurrent state a slot), so the window group and `state_slot` are given
to that LM's engine as those facts and STAGED, never run: the graphs that
do read them are compiled and served end to end, through the same path,
by tests/test_mimo_v2_flash_serving.py and tests/test_solar_open2.py. The
same goes for a chunk laid out as rows (tests/test_serving.py runs it).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import telemetry
from flexflow_tpu.serving.paged import BlockManager

from small_lms import build_lm, complete_every_step_at_once, engine

SERVE = dict(slots=2, max_seq_len=32, prefill_chunk=4, kv_layout="paged",
             kv_block_size=4)
PROMPTS = [[3, 7, 11, 2, 5, 9, 4], [5, 2], [1, 9, 30, 30, 12]]
# what the parent's engine (87b85d6, an array a put, `_feed`, the split
# and `_keep` each a program) generates from PROMPTS under --seed 11, 6
# new tokens a request, at temperature 0 and at 0.8
PARENT = {
    0.0: [[49, 33, 19, 33, 19, 33], [27, 58, 27, 50, 58, 27],
          [33, 11, 33, 13, 40, 33]],
    0.8: [[15, 51, 42, 31, 19, 27], [63, 52, 16, 32, 27, 16],
          [33, 14, 32, 46, 2, 25]],
}
LAYOUTS = ("paged", "rows", "window", "state")


@pytest.fixture(scope="module")
def model():
    return build_lm(batch=2, sequence_length=32, argv=["--seed", "11"])


def serve(ff, layout="paged", **kw):
    """An engine of the plain LM with the facts of `layout` (the module's
    docstring): a new one, since those facts are set on it from outside."""
    eng = ff.serve(**{**SERVE, **kw})
    if layout == "rows":
        eng._chunk_rows = True
    elif layout == "window":
        mgr = eng.block_manager
        eng.block_manager = BlockManager(
            mgr.num_blocks, mgr.block_size, mgr.table_width,
            window_blocks=mgr.num_blocks, window=6,
            window_span=eng.spec.prefill_chunk)
    elif layout == "state":
        eng._state_bytes_slot = 64
    return eng


def scheduled(eng):
    """The next step as `step()` would dispatch it, its blocks made the
    slots' own, nothing run on the device."""
    step = eng._schedule()
    for slot, positions in step.writes.items():
        eng.block_manager.ensure_writable(slot, positions)
    return step


def four_steps(eng, temperature=0.0):
    """Two requests' first steps, each dispatched in name only: a chunk
    that ends a prompt; the other prompt's first chunk beside the first
    request's decode row, whose token is still on the device; its last
    chunk; a step that only decodes."""
    eng.submit([5, 2, 8], temperature=temperature, max_new_tokens=8)
    eng.submit([1, 9, 30, 30, 12, 4], temperature=0.0, max_new_tokens=8)
    for n in range(4):
        step = scheduled(eng)
        assert (step.chunk is None) == (n == 3)
        assert step.from_sampled.sum() == (0, 1, 1, 2)[n]
        yield step
        eng._advance(step)


def the_plain_way(eng, step, sampled):
    """What the parent staged for `step`: `_stage_inputs`, the token
    column taken from `sampled` where the host does not hold it, the
    host's `read_idx` and temperatures."""
    slots = eng.spec.slots
    xs = eng._stage_inputs(step.tokens, step.positions, step.row_slots)
    tokens = xs[eng._token_input]
    column = jnp.where(step.from_sampled, sampled, tokens[:slots, 0])
    xs[eng._token_input] = jax.device_put(
        tokens.at[:slots, 0].set(column), tokens.sharding)
    temp = np.zeros((slots,), np.float32)
    for s in eng.scheduler.active_slots:
        temp[s.index] = s.request.temperature
    if step.row_slots is not None:
        temp = temp[step.row_slots]
    return xs, jnp.asarray(step.read_idx, jnp.int32), jnp.asarray(temp)


def assert_same_array(got, want, name):
    assert got.shape == want.shape and got.dtype == want.dtype, name
    assert got.sharding == want.sharding, (name, got.sharding)
    assert got.committed == want.committed, name
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want), name)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_packed_path_stages_what_the_plain_one_does(model, layout):
    eng = serve(model, layout)
    feeds = {"tokens", "positions", "page_table"} | {
        "window": {"page_table_w"}, "state": {"state_slot"}}.get(
            layout, set())
    for n, step in enumerate(four_steps(eng, temperature=0.7)):
        sampled = jax.device_put(np.asarray([41, 17], np.int32),
                                 eng._sampled.sharding)
        eng._sampled = sampled
        assert (step.row_slots is not None) == (layout == "rows" and n < 3)
        xs, read_idx, sub, temp = eng._stage_step(step)
        want_xs, want_read_idx, want_temp = the_plain_way(eng, step, sampled)
        assert set(xs) == set(want_xs) == feeds
        for name in feeds:
            assert_same_array(xs[name], want_xs[name], name)
        assert_same_array(read_idx, want_read_idx, "read_idx")
        assert_same_array(temp, want_temp, "temp")
        assert not sub.committed and sub.dtype == jax.random.key(0).dtype
        # a chunk as rows says where its samples' rows are; `_keep` files
        # them by slot for the program in front of the next step
        if step.row_slots is None:
            assert step.filed is None
        else:
            vector = jnp.arange(100, 100 + len(step.row_slots))
            by_slot = np.asarray(eng._keep(vector, step.filed))
            for slot, row in enumerate(step.sampled_row):
                assert row < 0 or by_slot[slot] == 100 + row
    assert float(np.asarray(temp).max()) == np.float32(0.7)  # bit for bit
    # both slots' tokens are the ones the step before sampled
    assert np.asarray(xs["tokens"])[:, 0].tolist() == [41, 17]


@pytest.fixture
def fresh(model):
    """The shared engine of SERVE (tests/small_lms.py) as it was built:
    drained, its key's chain at the start. Without the prefix cache:
    nothing a request leaves in it shortens the next one's prefill, so a
    run's steps, and so its keys, are a new engine's."""
    return engine(model, **SERVE, prefix_cache=False)


def test_the_step_is_handed_the_parents_chain_of_keys(model, fresh):
    eng = fresh
    step, subs = eng._step_fn, []

    def spy(params, state, xs, read_idx, sub, temp):
        subs.append(np.asarray(jax.random.key_data(sub)))
        return step(params, state, xs, read_idx, sub, temp)

    eng._step_fn = spy
    try:
        eng.generate([[5, 2, 8]], max_new_tokens=5)
    finally:
        eng._step_fn = step
    assert len(subs) >= 5
    rng = jax.random.key(model.config.seed)
    for got in subs:
        rng, sub = jax.random.split(rng)
        np.testing.assert_array_equal(got, jax.random.key_data(sub))
    np.testing.assert_array_equal(jax.random.key_data(eng._rng),
                                  jax.random.key_data(rng))


@pytest.mark.parametrize("temperature", sorted(PARENT))
def test_generate_gives_the_parents_tokens(fresh, temperature):
    assert fresh.generate(PROMPTS, max_new_tokens=6,
                          temperature=temperature) == PARENT[temperature]


def test_a_host_function_in_the_steps_place_still_drains(fresh, monkeypatch):
    eng = complete_every_step_at_once(fresh, monkeypatch)
    assert eng.generate(PROMPTS, max_new_tokens=6) == PARENT[0.0]
    assert eng.stats()["steps_ahead"] == 0


def test_the_step_lowered_from_plain_staging_is_the_one_a_call_finds(
        model, caplog):
    """The benchmark's jobs lower the step ahead of its first call from
    `_stage_inputs`' arrays and uncommitted `read_idx`, key and
    temperatures: the step loop's call has to find that lowering."""
    import logging

    eng = serve(model)
    dec, slots = eng.decode_model, eng.spec.slots

    def lowered():
        return sum("Compiling jit(decode_step)" in r.getMessage()
                   for r in caplog.records)

    jax.config.update("jax_log_compiles", True)
    try:
        with caplog.at_level(logging.WARNING):
            for rows, q in ((slots, 1), (slots, 4)):
                xs = eng._stage_inputs(np.zeros((rows, q), np.int32),
                                       np.full((rows, q), 32, np.int32))
                eng._step_fn.lower(
                    dec._params, dec._state, xs,
                    jnp.zeros((rows,), jnp.int32), jax.random.key(0),
                    jnp.zeros((rows,), jnp.float32)).compile()
            assert lowered() == 2
            eng.generate([[1, 9, 30, 30, 12, 4, 6, 2]], max_new_tokens=3)
    finally:
        jax.config.update("jax_log_compiles", False)
    assert eng.stats()["iterations"] >= 4
    assert lowered() == 2
    # and `feed` is one build a step shape: the key it is first handed
    # lies where the key it hands back does
    assert [p.feed._cache_size() for p in eng._packings.values()] == [1, 1]


def test_on_two_devices_the_feeds_lie_where_the_search_put_them():
    ff = build_lm(mesh=(2, 1, 1, 1), batch=2, sequence_length=32)
    eng = serve(ff)
    dec, mesh = eng.decode_model, eng.decode_model.executor.mesh
    assert mesh.devices.size == 2
    for step in four_steps(eng):
        xs, read_idx, sub, temp = eng._stage_step(step)
        want_xs, *_ = the_plain_way(eng, step, eng._sampled)
        for name, x in xs.items():
            assert x.sharding.spec == dec._input_partition_spec(name), name
            assert_same_array(x, want_xs[name], name)
        for x in (read_idx, sub, temp):
            assert x.sharding.is_fully_replicated
            assert set(x.sharding.device_set) == set(mesh.devices.flat)
    assert any(s is not None for s in dec._input_partition_spec("tokens"))


class Crossings:
    """Every way into the runtime that `ServingEngine._dispatch` has or
    had, counted by name in the order taken: the puts (`jax.device_put`,
    `jnp.asarray`, `jnp.array`), the engine's programs, the key's split."""

    def __init__(self, eng, monkeypatch):
        self.seen = seen = []

        def counted(name, fn):
            def call(*args, **kwargs):
                # a call under trace is part of a program, not one more
                if not any(isinstance(a, jax.core.Tracer) for a in args):
                    seen.append(name)
                return fn(*args, **kwargs)
            return call

        for module, names in ((jax, ("device_put",)),
                              (jnp, ("asarray", "array")),
                              (jax.random, ("split", "key"))):
            for name in names:
                monkeypatch.setattr(module, name, counted(
                    "put" if module is not jax.random else name,
                    getattr(module, name)))
        eng._step_fn = counted("step", eng._step_fn)
        eng._keep = counted("keep", eng._keep)
        packing = eng._packing
        eng._packing = lambda rows, q: dataclasses.replace(
            p := packing(rows, q), feed=counted("feed", p.feed))


@pytest.mark.parametrize("layout", LAYOUTS)
def test_staging_a_step_is_one_put_and_one_program(model, layout,
                                                   monkeypatch):
    eng = serve(model, layout)
    steps = list(four_steps(eng))
    eng._rng = jax.random.key(0)
    crossings = Crossings(eng, monkeypatch)
    # a put that no call names (a host array handed to a program) raises
    with jax.transfer_guard_host_to_device("disallow"):
        for step in steps:
            eng._stage_step(step)
    assert crossings.seen == ["put", "feed"] * 4
    assert (eng._stage_puts, eng._stage_programs) == (4, 4)


def test_a_step_in_steady_state_is_one_put_one_program_and_the_launch(
        fresh, monkeypatch):
    eng = fresh
    reqs = [eng.submit(p, max_new_tokens=6) for p in PROMPTS[:2]]
    for _ in range(4):  # past both prompts' chunks: two slots decode
        eng.step()
    eng.reset_stats()
    eng.step()
    spans = []
    span = telemetry.span
    monkeypatch.setattr(telemetry, "span", lambda name, **args: (
        spans.append((name, args)), span(name, **args))[1])
    crossings = Crossings(eng, monkeypatch)
    with jax.transfer_guard_host_to_device("disallow"):
        eng.step()
    assert crossings.seen == ["put", "feed", "step"]
    stats = eng.stats()
    assert stats["iterations"] == 2
    assert (stats["stage_puts"], stats["stage_programs"]) == (2, 2)
    outer = [args for name, args in spans
             if name == "serve.stage" and "part" not in args]
    assert [(a["puts"], a["programs"]) for a in outer] == [(1, 1)]
    assert [args["part"] for name, args in spans
            if name == "serve.stage" and "part" in args] == [
                "build", "put", "feed"]
    eng.run_until_drained()
    assert [r.generated for r in reqs] == PARENT[0.0][:2]
