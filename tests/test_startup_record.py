"""The start-up record (flexflow_tpu/telemetry/startup.py): the phases
compile() and serve() leave in it, the builds JAX reports into it, what a
recompile is called, its cap, and that nothing a step does reaches it.

Every test but the first runs against a record of its own: the process's
one has whatever the tests before left in it, and may be full.
"""

import json
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import telemetry
from flexflow_tpu.telemetry import startup
from flexflow_tpu.telemetry.tracer import Tracer

BUILDS = (startup.TRACE, startup.LOWER, startup.BACKEND)


@pytest.fixture
def record(monkeypatch):
    """A fresh record in the process-wide one's place."""
    fresh = Tracer(max_events=startup.CAP)
    monkeypatch.setattr(startup, "_record", fresh)
    monkeypatch.setattr(startup, "_built", {})
    monkeypatch.setattr(startup, "_rebuilt_logged", set())
    monkeypatch.setattr(startup, "_stepping", False)
    return fresh


def phases():
    return [e for e in startup.events() if e[0] not in BUILDS]


def builds_of(program):
    return [e for e in startup.events()
            if e[0] in BUILDS and e[4].get("program") == program]


def inside(child, parent) -> bool:
    return (child[3] == parent[3] and parent[1] <= child[1]
            and child[2] <= parent[2])


def batches(rows):
    rs = np.random.RandomState(0)
    x = {"tokens": rs.randint(0, 61, (rows, 8)).astype(np.int32),
         "positions": np.tile(np.arange(8, dtype=np.int32), (rows, 1))}
    return x, rs.randint(0, 61, (rows, 8, 1)).astype(np.int32)


def tiny_lm(extra=()):
    from flexflow_tpu import (
        FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
    )
    from flexflow_tpu.models import TransformerLMConfig, build_transformer_lm

    argv = sys.argv
    sys.argv = ["t", "-b", "2", "--mesh", "1,1,1,1", *extra]
    try:
        config = FFConfig()
    finally:
        sys.argv = argv
    ff = FFModel(config)
    build_transformer_lm(
        ff, TransformerLMConfig(
            vocab_size=61, hidden_size=16, num_heads=2, num_layers=1,
            mlp_ratio=2, sequence_length=8, attention_impl="xla"),
        batch_size=2)
    ff.compile(
        optimizer=SGDOptimizer(lr=0.1),
        loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY])
    return ff


def test_the_import_is_the_records_first_phase():
    first = startup.events()[0] if not startup.dropped() else None
    found = [e for e in startup.events() if e[0] == "import"]
    assert len(found) == 1 and found[0][2] > found[0][1]
    import flexflow_tpu

    assert found[0][1] == pytest.approx(flexflow_tpu._IMPORT_T0, abs=1e-6)
    # nothing of the record starts before the package's first line
    assert first is None or first[1] >= flexflow_tpu._IMPORT_T0 - 1e-6


def test_compile_leaves_each_phase_nested_and_in_order(record, tmp_path):
    from test_warmstart import SEARCH_ARGV, _build

    _build(SEARCH_ARGV + ["--warmstart-dir", str(tmp_path / "ws"),
                          "--calibrate", "2", "--spmd-barrier"])
    found = phases()
    root = found[-1]
    assert root[0] == "compile" and root[4] == {"comp_mode": "training"}
    assert [e[0] for e in found[:-1]] == [
        "compile.graph", "warmstart.plan_lookup",
        "warmstart.calibration_load", "compile.calibrate",
        "warmstart.plan_lookup", "compile.search", "warmstart.store",
        "compile.update_sharding", "compile.executor", "compile.verify",
        "compile.spmd_barrier", "compile.init"]
    assert all(inside(child, root) for child in found[:-1])
    starts = [e[1] for e in found[:-1]]
    assert starts == sorted(starts)
    search = next(e for e in found if e[0] == "compile.search")
    assert search[4] == {"mode": "joint"}
    assert [e[4]["layer"] for e in found
            if e[0] == "warmstart.plan_lookup"] == ["checkpoint", "cache"]
    # the initializers' programs were built inside compile.init
    init = next(e for e in found if e[0] == "compile.init")
    assert any(e[0] == startup.BACKEND and inside(e, init)
               for e in startup.events())


def test_serve_leaves_serve_compile_with_its_four(record):
    ff = tiny_lm()
    del startup._record._events[:]
    ff.serve(slots=2, max_seq_len=8, prefill_chunk=4, kv_block_size=4)
    found = phases()
    root = found[-1]
    assert root[0] == "serve.compile" and root[4] == {"slots": 2}
    children = [e for e in found[:-1] if not e[0].startswith("compile")]
    assert [e[0] for e in children] == [
        "serve.graph", "serve.adopt", "serve.step_fn", "serve.pool"]
    assert all(inside(child, root) for child in children)
    # the decode graph's own compile nests under serve.graph as it runs
    graph = children[0]
    nested = [e for e in found if e[0] == "compile"]
    assert len(nested) == 1 and inside(nested[0], graph)
    assert nested[0][4] == {"comp_mode": "inference"}


def test_a_first_call_leaves_three_builds_and_a_second_nothing(record):
    @jax.jit
    def startup_probe_once(x):
        return jnp.tanh(x) * 2.0

    startup_probe_once(jnp.ones((3,))).block_until_ready()
    found = builds_of("startup_probe_once")
    assert sorted(e[0] for e in found) == sorted(BUILDS)
    assert all(e[2] > e[1] for e in found)
    # trace, then lower, then the backend, each ending where it was heard
    assert [e[0] for e in found] == list(BUILDS)
    before = len(startup.events())
    startup_probe_once(jnp.ones((3,))).block_until_ready()
    assert len(startup.events()) == before


def test_trace_events_nest_inside_the_outer_programs(record):
    @jax.jit
    def startup_probe_outer(x):
        return jnp.where(x > 0, jnp.tanh(x), x)

    startup_probe_outer(jnp.ones((4,))).block_until_ready()
    (outer,) = [e for e in builds_of("startup_probe_outer")
                if e[0] == startup.TRACE]
    nested = [e for e in startup.events()
              if e[0] == startup.TRACE and e is not outer
              and inside(e, outer)]
    assert nested, "a function called under jit reports its own trace"
    traces = [(e[1], e[2]) for e in [outer, *nested]]
    assert startup.union_seconds(traces) < sum(b - a for a, b in traces)
    assert startup.union_seconds(traces) == pytest.approx(
        outer[2] - outer[1])


def test_a_new_shape_builds_again_and_is_warned_of_once(record, capsys):
    @jax.jit
    def startup_probe_reshaped(x):
        return x + 1

    startup_probe_reshaped(jnp.ones((2,)))
    startup.steps_began()
    capsys.readouterr()
    startup_probe_reshaped(jnp.ones((2,)))       # built: nothing
    assert "startup_probe_reshaped" not in capsys.readouterr().err
    startup_probe_reshaped(jnp.ones((5,)))
    found = builds_of("startup_probe_reshaped")
    assert sorted(e[0] for e in found) == sorted(BUILDS * 2)
    err = capsys.readouterr().err
    assert err.count("startup_probe_reshaped was built again") == 1
    assert "trace" in err and "lower" in err and "backend" in err
    startup_probe_reshaped(jnp.ones((7,)))       # once a program
    assert sum(e[0] == startup.BACKEND
               for e in builds_of("startup_probe_reshaped")) == 3
    assert "startup_probe_reshaped" not in capsys.readouterr().err


def test_a_build_before_steps_began_is_no_recompile(record, capsys):
    @jax.jit
    def startup_probe_bucketed(x):
        return x * 3

    capsys.readouterr()
    for n in (2, 4, 8):                 # an executable a bucket: set-up
        startup_probe_bucketed(jnp.ones((n,)))
    assert "built again" not in capsys.readouterr().err
    assert sum(e[0] == startup.BACKEND
               for e in builds_of("startup_probe_bucketed")) == 3


def test_builds_from_two_threads_keep_their_thread_ids(record):
    @jax.jit
    def startup_probe_threaded(x):
        return x - 1

    tids = {}

    def build(n):
        tids[n] = threading.get_ident()
        startup_probe_threaded.lower(
            jax.ShapeDtypeStruct((n,), jnp.float32)).compile()

    workers = [threading.Thread(target=build, args=(n,)) for n in (3, 6)]
    for w in workers:
        w.start()
    for w in workers:
        w.join(timeout=120)
    assert not any(w.is_alive() for w in workers)
    assert tids[3] != tids[6] != threading.get_ident()
    by_tid = {}
    for e in builds_of("startup_probe_threaded"):
        by_tid.setdefault(e[3], []).append(e[0])
    assert set(by_tid) == set(tids.values())
    assert all(sorted(kinds) == sorted(BUILDS) for kinds in by_tid.values())


def test_a_cache_read_is_an_argument_of_the_backend_event(record):
    startup._on_duration(startup._CACHE_READ, 0.25)
    startup._on_duration(
        "/jax/core/compile/backend_compile_duration", 0.5,
        fun_name="jit(startup_probe_cached)")
    startup._on_duration(
        "/jax/core/compile/backend_compile_duration", 0.5,
        fun_name="jit(startup_probe_cached)")
    first, second = builds_of("startup_probe_cached")
    assert first[4] == {"program": "startup_probe_cached",
                        "cache_read_s": 0.25}
    assert second[4] == {"program": "startup_probe_cached"}
    assert first[2] - first[1] == pytest.approx(0.5)
    startup._on_duration("/jax/some/other/duration", 1.0)
    assert len(startup.events()) == 2


def test_past_the_cap_events_are_dropped_and_counted(monkeypatch):
    small = Tracer(max_events=4)
    monkeypatch.setattr(startup, "_record", small)
    for i in range(10):
        with telemetry.phase("compile.graph", i=i):
            pass
    # a thread's name is an event of the buffer too
    assert len(startup.events()) == 3 and startup.dropped() == 7
    assert startup.summary()["startup_dropped_events"] == 7

    @jax.jit
    def startup_probe_dropped(x):
        return x + 2

    startup_probe_dropped(jnp.ones((2,)))        # heard, dropped, no raise
    assert startup.dropped() >= 10


def test_ten_steady_state_engine_steps_add_nothing(record):
    ff = tiny_lm()
    engine = ff.serve(slots=2, max_seq_len=8, prefill_chunk=4,
                      kv_block_size=4)
    prompts = [[1, 2, 3], [4, 5, 6]]
    engine.generate(prompts, max_new_tokens=3)   # every shape, once
    for p in prompts:
        engine.submit(p, max_new_tokens=3)
    engine.step()
    before = len(startup.events())
    for _ in range(10):
        if engine.scheduler.drained:
            for p in prompts:
                engine.submit(p, max_new_tokens=3)
        engine.step()
    assert len(startup.events()) == before
    assert startup.dropped() == 0


def test_ten_steady_state_fit_steps_add_nothing(record):
    ff = tiny_lm()
    x, y = batches(20)
    ff.fit(x, y, epochs=1)                       # ten steps, the first builds
    assert builds_of("train_step") or builds_of("_train_step_body")
    before = len(startup.events())
    ff.fit(x, y, epochs=1)                       # ten more
    assert len(startup.events()) == before
    assert startup.dropped() == 0


def test_span_with_no_session_is_the_bare_annotation(record):
    from jax.profiler import TraceAnnotation

    assert telemetry.active_session() is None
    assert type(telemetry.span("step", step=1)) is TraceAnnotation
    with telemetry.span("step", step=1):
        pass
    assert startup.events() == []


def test_a_phase_is_a_span_and_an_interval_of_the_record(record, tmp_path):
    session = telemetry.TelemetrySession(str(tmp_path))
    telemetry.activate(session)
    try:
        with telemetry.phase("resume.restore", path="p", tree={"no": 1}):
            with telemetry.span("step"):
                pass
    finally:
        telemetry.deactivate(session)
    (only,) = startup.events()
    # a phase keeps its scalar arguments; a span inside it is not one
    assert only[0] == "resume.restore" and only[4] == {"path": "p"}
    assert only[3] == threading.get_ident()
    names = [e[0] for e in session.tracer.intervals()]
    assert names == ["step", "resume.restore"]
    session.close()
    with open(tmp_path / "startup_trace.json") as f:
        dumped = json.load(f)["traceEvents"]
    assert [e["name"] for e in dumped if e["ph"] == "X"] == [
        "resume.restore"]


def test_a_phase_that_raises_is_recorded_and_raises(record):
    with pytest.raises(KeyError):
        with telemetry.phase("compile.verify"):
            raise KeyError("refused")
    assert [e[0] for e in startup.events()] == ["compile.verify"]


def test_the_fit_summary_carries_the_start_up(record, tmp_path):
    ff = tiny_lm(extra=("--telemetry-dir", str(tmp_path)))
    x, y = batches(4)
    ff.fit(x, y, epochs=1)
    (summary,) = [r for r in telemetry.read_jsonl(
        str(tmp_path / "metrics.jsonl")) if r["kind"] == "summary"]
    assert summary["time_to_first_step_s"] > 0
    own = summary["startup_phase_self_s"]
    assert own["compile.init"] > 0 and own["compile"] >= 0
    assert set(summary["startup_build_s"]) == {"trace", "lower", "backend"}
    assert all(v > 0 for v in summary["startup_build_s"].values())
    assert summary["startup_programs"] >= 1
    assert summary["startup_dropped_events"] == 0
    ff.get_telemetry().close()
    assert (tmp_path / "startup_trace.json").exists()


@pytest.mark.parametrize("intervals, own, union", [
    ([], [], 0.0),
    ([(0.0, 10.0)], [10.0], 10.0),
    # a child and a build inside it
    ([(0.0, 10.0), (2.0, 6.0), (3.0, 4.0)], [6.0, 3.0, 1.0], 10.0),
    # two children side by side, one touching the parent's end
    ([(0.0, 10.0), (1.0, 2.0), (8.0, 10.0)], [7.0, 1.0, 2.0], 10.0),
    # apart, and an empty one
    ([(0.0, 1.0), (5.0, 7.0), (6.0, 6.0)], [1.0, 2.0, 0.0], 3.0),
    # a child whose edge the clocks pushed past its parent's
    ([(1.0, 5.0), (0.9, 2.0)], [3.0, 1.1], 4.1),
])
def test_self_time_and_union(intervals, own, union):
    assert startup.self_seconds(intervals) == pytest.approx(own)
    assert startup.union_seconds(intervals) == pytest.approx(union)
