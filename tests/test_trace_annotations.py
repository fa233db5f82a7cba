"""telemetry.span on the profiler's clock (docs/observability.md, "XProf
handoff"): every span is a `jax.profiler.TraceAnnotation` named
`ff/<name>`, so a profiler trace (`--xprof-dir`, the benchmark's
`--trace 1`) holds the program's spans on its host plane beside the device
lines. Under a CPU trace with the Python tracer off (as the benchmark
sets it): the fit loop's and the engine step's spans are there, children
inside parents, with the arguments the benchmark's readers use.
"""

import glob
import json
import os

import jax
import pytest

from flexflow_tpu import telemetry
from flexflow_tpu.telemetry.tracer import Tracer

from small_lms import (
    ROWS, build_lm, build_rows_lm, complete_every_step_at_once, engine,
)
from test_telemetry import _build_mlp, _train_data

ENGINE_PHASES = ("ff/serve.schedule", "ff/serve.prepare_writes",
                 "ff/serve.stage", "ff/serve.dispatch", "ff/serve.fetch",
                 "ff/serve.bookkeep")


@pytest.fixture(autouse=True)
def _no_session_leak():
    yield
    telemetry.deactivate()


def program_spans(trace_dir):
    """[(name, start_ns, end_ns, stats)] of the host plane's `ff/`
    events, by start."""
    (path,) = glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(path)
    (host,) = [p for p in profile.planes if p.name == "/host:CPU"]
    return sorted(
        ((e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
         for line in host.lines for e in line.events
         if e.name.startswith("ff/")), key=lambda s: (s[1], -s[2]))


class traced:
    """`with traced(dir):` a profiler trace as the benchmark takes one."""

    def __init__(self, trace_dir):
        self.trace_dir = str(trace_dir)

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)

    def __exit__(self, *exc):
        jax.profiler.stop_trace()


def inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


def named(spans, *names):
    return [s for s in spans if s[0] in names]


def whole(spans):
    """Without the parts of `serve.stage`: spans of its name inside it,
    with a `part`."""
    return [s for s in spans if "part" not in s[3]]


def test_fit_spans_reach_the_profilers_host_plane(tmp_path):
    ff = _build_mlp(tmp_path)
    x, y = _train_data(n=128)
    ff.fit(x, y, epochs=1, batch_size=32, verbose=False)   # compiles
    with traced(tmp_path / "trace"):
        ff.fit(x, y, epochs=1, batch_size=32, verbose=False)
    spans = program_spans(tmp_path / "trace")
    (fit,) = named(spans, "ff/fit")
    assert fit[3] == {"steps": 4, "batch_size": 32}
    steps, waits = named(spans, "ff/step"), named(spans, "ff/data_wait")
    (drain,) = named(spans, "ff/fit.drain")
    assert [s[3]["step"] for s in steps] == [5, 6, 7, 8]
    assert len(waits) == 4
    for step, wait in zip(steps, waits):
        assert inside(step, fit) and inside(wait, step)
    for a, b in zip(steps, steps[1:]):
        assert a[2] <= b[1]
    assert inside(drain, fit) and drain[1] >= steps[-1][2]


@pytest.mark.parametrize("at_once", [True, False])
@pytest.mark.parametrize("layout", ["rectangle", "rows"])
def test_engine_phases_reach_the_profilers_host_plane(
        tmp_path, monkeypatch, layout, at_once):
    """Two requests on two slots, prompts of 5 and 2 tokens, 3 new tokens
    each, chunks of 4: five steps, whose `kv_rows` (the context rows
    the step's attention must read) are counted by hand below, the same
    in both layouts of a chunk step; laid out as rows, a chunk step also
    says how many rows it ran and how many context rows its kernels read
    (`kv_rows` again where the chunk kernel takes the chunk's rows).
    Completed at once, a step is an iteration with all six phases, the
    device call's three inside the step's span. Left in flight, a step is
    dispatched by one iteration and fetched by the next, whose span it
    is: that span covers the next step's schedule, stage and dispatch
    and its own fetch."""
    rows = layout == "rows"
    ff = build_rows_lm() if rows else build_lm(batch=1)
    eng = engine(ff, slots=2, max_new_tokens=3, prefill_chunk=4,
                 prefix_sharing=False,
                 **(ROWS if rows else {"kv_block_size": 4}))
    assert eng._chunk_rows == rows
    if at_once:
        complete_every_step_at_once(eng, monkeypatch)
    eng.generate([[9, 8, 7]])                       # compiles
    first = eng._iterations
    with traced(tmp_path / "trace"):
        eng.generate([[3, 7, 11, 2, 5], [5, 2]])
    spans = program_spans(tmp_path / "trace")
    iterations = named(spans, "ff/serve.iteration")
    # in flight, a sixth call fetches the fifth step
    assert [s[3]["iteration"] for s in iterations] == [
        first + i for i in range(1, 6 if at_once else 7)]
    calls = named(spans, "ff/serve.prefill", "ff/serve.step")
    assert [c[0] for c in calls] == ["ff/serve.prefill"] * 3 + [
        "ff/serve.step"] * 2
    # chunk 0-4 of the first prompt; its last token; the second prompt
    # beside the first's 5 + 1 rows; 7 + 3; the second alone, 4
    assert [c[3]["kv_rows"] for c in calls] == [4, 5, 8, 10, 4]
    assert [c[3]["admitted"] for c in calls] == [2, 0, 0, 0, 0]
    assert {c[3]["pending"] for c in calls} == {0}
    assert {c[3]["kv_itemsize"] for c in calls} == {4}   # an fp32 pool
    # a request's chunks share its id (the one its instants carry too)
    traces = [c[3]["trace"] for c in calls[:3]]
    assert traces[0] == traces[1] != traces[2]
    assert all(t.startswith("req-") for t in traces)
    assert [c[3]["tokens"] for c in calls[:3]] == [4, 1, 2]
    assert calls[3][3]["active"] == 2
    if rows:
        # two slots + the chunk's bucket; the chunk kernel reads the
        # chunk's context once for all its rows: 4; 5; 2 beside the first
        # request's 6 (row by row through the single-query kernel it was
        # 1+2+3+4; 5; (1+2) + 6: tests/test_paged_chunk_attention.py)
        assert [c[3]["rows"] for c in calls[:3]] == [6, 3, 4]
        assert [c[3]["kv_rows_walked"] for c in calls[:3]] == [4, 5, 8]
    assert not any("rows" in c[3] or "kv_rows_walked" in c[3]
                   for c in calls[0 if not rows else 3:])
    phases_of = [[s for s in whole(named(spans, *ENGINE_PHASES))
                  if inside(s, it)] for it in iterations]
    for phases in phases_of:
        for a, b in zip(phases, phases[1:]):        # disjoint, in order
            assert a[2] <= b[1]
    ahead = [f[3]["ahead"] for f in named(spans, "ff/serve.fetch")]
    if at_once:
        assert ahead == [0] * 5
        for it, call, phases in zip(iterations, calls, phases_of):
            assert [p[0] for p in phases] == list(ENGINE_PHASES)
            assert inside(call, it)
            stage, dispatch, fetch = phases[2:5]
            assert all(inside(p, call) for p in (stage, dispatch, fetch))
            assert (not inside(phases[0], call)
                    and not inside(phases[5], call))
    else:
        # every fetch but the last finds the next step dispatched
        assert ahead == [1, 1, 1, 1, 0]
        # the first call dispatches and fetches nothing; the last finds
        # no row to run and fetches
        assert [[p[0] for p in phases] for phases in phases_of] == [
            list(ENGINE_PHASES[:4]), *[list(ENGINE_PHASES)] * 4,
            [ENGINE_PHASES[0], *ENGINE_PHASES[4:]]]
        # a step has one span: the first step's in the call that
        # dispatched it (nothing was in flight), every other in the call
        # that fetches it, the call after the one that dispatched it
        assert inside(calls[0], iterations[0])
        assert all(inside(p, calls[0]) for p in phases_of[0][2:4])
        assert not named([s for s in spans if inside(s, iterations[1])],
                         "ff/serve.prefill", "ff/serve.step")
        for it, call, phases in zip(iterations[2:], calls[1:],
                                    phases_of[2:]):
            assert inside(call, it)
            assert all(inside(p, call) for p in phases[:-1])
            assert not inside(phases[-1], call)
    # the copy-on-write dispatch sits inside its phase
    prepare = named(spans, "ff/serve.prepare_writes")
    for copy in named(spans, "ff/serve.cow_copy"):
        assert any(inside(copy, p) for p in prepare)


@pytest.mark.parametrize("at_once", [True, False])
@pytest.mark.parametrize("layout", ["rectangle", "rows"])
def test_every_span_of_a_step_carries_its_id(tmp_path, monkeypatch, layout,
                                             at_once):
    """Three requests on two slots (the third is admitted when the first
    ends, and the first ends by EOS), chunks of 4, then the drain: a step
    gets its id when it is scheduled, the ids are consecutive, every
    span of a step carries its step's and no other, the parts of
    `serve.stage` (spans of its name with a `part`) lie inside it, and
    `serve.dispatch` says what the step ran."""
    rows = layout == "rows"
    ff = build_rows_lm() if rows else build_lm(batch=1)
    eng = engine(ff, slots=2, max_new_tokens=4, prefill_chunk=4,
                 prefix_sharing=False,
                 **(ROWS if rows else {"kv_block_size": 4}))
    if at_once:
        complete_every_step_at_once(eng, monkeypatch)
    prompts = [[3, 7, 11, 2, 5], [5, 2], [9, 8, 7]]
    (alone,) = eng.generate(prompts[:1])            # compiles
    before = eng._step_ids
    eng.reset_stats()                               # the ids go on
    with traced(tmp_path / "trace"):
        replies = eng.generate(prompts, eos_id=alone[1])
    assert replies[0] == alone[:2] and eng.stats()["iterations"] > 0
    spans = program_spans(tmp_path / "trace")
    dispatches = named(spans, "ff/serve.dispatch")
    ids = [d[3]["step"] for d in dispatches]
    assert ids == list(range(before + 1, before + 1 + len(ids)))
    assert eng._step_ids == ids[-1] == before + eng.stats()["iterations"]
    once = ("ff/serve.schedule", "ff/serve.prepare_writes", "ff/serve.stage",
            "ff/serve.dispatch", "ff/serve.advance", "ff/serve.fetch",
            "ff/serve.bookkeep")
    of_a_step = named(spans, *once, "ff/serve.step", "ff/serve.prefill")
    assert all("step" in s[3] for s in of_a_step)
    for d in dispatches:
        mine = [s for s in of_a_step if s[3]["step"] == d[3]["step"]]
        call = named(mine, "ff/serve.step", "ff/serve.prefill")
        assert sorted(s[0] for s in whole(mine)) == sorted(
            [*once, call[0][0]])
        (stage,) = whole(named(mine, "ff/serve.stage"))
        parts = [s for s in named(mine, "ff/serve.stage") if "part" in s[3]]
        assert [p[3]["part"] for p in parts] == ["build", "put", "feed"]
        assert (stage[3]["puts"], stage[3]["programs"]) == (1, 1)
        assert all(inside(p, stage) for p in parts)
        for a, b in zip(parts, parts[1:]):          # disjoint, in order
            assert a[2] <= b[1]
        # what the step is, against its own span's arguments
        args, (call,) = d[3], call
        assert args["program"] == (
            "jit_on_the_host" if at_once else "jit_decode_step")
        if call[0] == "ff/serve.step":
            assert (args["kind"], args["rows"]) == ("decode", 2)
            assert "bucket" not in args and "chunk_start" not in args
        else:
            bucket = eng._bucket(call[3]["tokens"])
            assert (args["kind"], args["bucket"], args["chunk_start"]) == (
                "chunk", bucket, call[3]["start"])
            assert args["rows"] == (2 + bucket if rows else 2)
            assert call[3].get("rows", 2) == args["rows"]
    # the call that finds no row to run asks for the next id and makes
    # no step of it
    scheduled = [s[3]["step"] for s in named(spans, "ff/serve.schedule")]
    assert scheduled[:len(ids)] == ids
    assert set(scheduled[len(ids):]) <= {ids[-1] + 1}
    kinds = [d[3]["kind"] for d in dispatches]
    assert kinds.count("chunk") == 4 and kinds.count("decode") >= 3


def test_kv_itemsize_is_what_attention_reads():
    """Under --dtype bf16 the decode graph declares its pool in the compute
    dtype (serving/decode_graph.py), so attention reads the pool as it
    lies: two bytes an element are stored and two are read."""
    ff = build_lm(batch=1, argv=["--dtype", "bf16"])
    eng = engine(ff, slots=2, max_new_tokens=2, prefill_chunk=4)
    (pool,) = {ws["pool_k"].dtype.itemsize
               for ws in eng.decode_model._state.values() if "pool_k" in ws}
    assert (pool, eng._kv_itemsize) == (2, 2)


def test_an_idle_step_opens_no_iteration(tmp_path):
    ff = build_lm(batch=1)
    eng = engine(ff, slots=2, max_new_tokens=2, prefill_chunk=4)
    before = eng._iterations
    with traced(tmp_path / "trace"):
        assert eng.step() == []
    assert eng._iterations == before
    assert not named(program_spans(tmp_path / "trace"),
                     "ff/serve.iteration")


def test_a_session_records_what_it_did_and_the_annotation_takes_scalars(
        tmp_path, monkeypatch):
    """With a session on, trace.json holds the span with all its
    arguments, as before; the profiler's annotation gets the int, float,
    bool and str ones only."""
    seen = []
    real = telemetry.TraceAnnotation

    def spy(name, **kwargs):
        seen.append((name, kwargs))
        return real(name, **kwargs)

    monkeypatch.setattr(telemetry, "TraceAnnotation", spy)
    session = telemetry.TelemetrySession(str(tmp_path / "tel"))
    telemetry.activate(session)
    with telemetry.span("outer", step=3, rate=0.5, on=True, tag="a",
                        shape=(2, 3)):
        with telemetry.span("inner"):
            pass
    telemetry.deactivate(session)
    session.flush()
    assert seen == [("ff/outer", {"step": 3, "rate": 0.5, "on": True,
                                  "tag": "a"}), ("ff/inner", {})]
    with open(tmp_path / "tel" / "trace.json") as f:
        events = {e["name"]: e for e in json.load(f)["traceEvents"]
                  if e["ph"] == "X"}
    assert set(events) == {"outer", "inner"}
    assert events["outer"]["args"] == {
        "step": 3, "rate": 0.5, "on": True, "tag": "a", "shape": [2, 3]}
    assert "args" not in events["inner"]
    outer, inner = events["outer"], events["inner"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3


def test_no_profiler_and_no_session_touches_no_tracer(monkeypatch):
    monkeypatch.setattr(Tracer, "span", None)
    monkeypatch.setattr(Tracer, "_complete", None)
    assert telemetry.active_session() is None
    with telemetry.span("serve.stage", rows=123, decoding=16):
        with telemetry.span("serve.fetch"):
            pass
