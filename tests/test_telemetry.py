"""Telemetry subsystem: tracer/recorder units + instrumented-fit integration.

Covers the observability acceptance surface: span nesting and
thread-safety of the Chrome-trace tracer, the JSONL schema, and an
end-to-end `fit` with --telemetry-dir producing (a) a trace that parses as
Chrome trace-event JSON with compile/step/data-wait/checkpoint spans and
(b) step records carrying the data-wait and save-latency split plus a
p50/p95 summary.
"""

import json
import sys
import threading

import numpy as np
import pytest

from flexflow_tpu import telemetry
from flexflow_tpu.telemetry import log as fflog
from flexflow_tpu.telemetry.recorder import MetricsRecorder, read_jsonl
from flexflow_tpu.telemetry.tracer import Tracer


@pytest.fixture(autouse=True)
def _no_session_leak():
    """A session activated by one test must not instrument the next."""
    yield
    telemetry.deactivate()


def _events(tracer, ph=None):
    evs = tracer.to_dict()["traceEvents"]
    return [e for e in evs if ph is None or e.get("ph") == ph]


# ---------------------------------------------------------------- tracer

@pytest.mark.quick
def test_tracer_span_nesting():
    tr = Tracer()
    with tr.span("outer", phase="compile"):
        with tr.span("inner"):
            pass
        with tr.span("inner2"):
            pass
    xs = {e["name"]: e for e in _events(tr, "X")}
    assert set(xs) == {"outer", "inner", "inner2"}
    out, inn, inn2 = xs["outer"], xs["inner"], xs["inner2"]
    # children fall inside the parent interval (Perfetto nests on this)
    for child in (inn, inn2):
        assert child["ts"] >= out["ts"]
        assert child["ts"] + child["dur"] <= out["ts"] + out["dur"] + 1e-3
    assert inn2["ts"] >= inn["ts"] + inn["dur"] - 1e-3
    assert out["args"] == {"phase": "compile"}


@pytest.mark.quick
def test_tracer_thread_safety():
    tr = Tracer()
    n_threads, n_spans = 8, 200
    errors = []
    gate = threading.Barrier(n_threads)

    def worker(i):
        try:
            gate.wait()  # all threads emit concurrently (distinct idents)
            for k in range(n_spans):
                with tr.span(f"w{i}", k=k):
                    pass
        except Exception as e:  # pragma: no cover - the assertion target
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    xs = _events(tr, "X")
    assert len(xs) == n_threads * n_spans
    # every event carries its emitting thread, and each thread got a
    # thread_name metadata record
    tids = {e["tid"] for e in xs}
    assert len(tids) == n_threads
    metas = [e for e in _events(tr, "M") if e["name"] == "thread_name"]
    assert tids <= {e["tid"] for e in metas}
    # the dump is valid JSON
    json.loads(json.dumps(tr.to_dict()))


@pytest.mark.quick
def test_tracer_counter_instant_and_cap(tmp_path):
    tr = Tracer(max_events=8)
    tr.counter("c", {"v": 1})
    tr.instant("marker", step=3)
    for _ in range(50):
        tr.instant("spam")
    path = tr.dump(str(tmp_path / "trace.json"))
    data = json.load(open(path))
    phs = {e["ph"] for e in data["traceEvents"]}
    assert {"C", "i", "M"} <= phs
    # over-cap events were dropped and the drop was surfaced
    dropped = [e for e in data["traceEvents"]
               if e["name"] == "tracer.dropped_events"]
    assert dropped and dropped[0]["args"]["dropped"] > 0


# ---------------------------------------------------------------- recorder

@pytest.mark.quick
def test_recorder_jsonl_schema(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    rec = MetricsRecorder(path)
    rec.record("manifest", mesh_axes={"data": 8}, git_sha="abc")
    rec.record("step", step=1, step_time_s=0.5, data_wait_s=0.1,
               save_latency_s=0.0)
    rec.close()
    recs = read_jsonl(path)
    assert [r["kind"] for r in recs] == ["manifest", "step"]
    for r in recs:
        assert isinstance(r["t"], float)
    assert recs[0]["mesh_axes"] == {"data": 8}
    assert recs[1]["step_time_s"] == 0.5
    # a late record after close is dropped, not an exception (the async
    # checkpoint writer can outlive the session)
    rec.record("late", x=1)
    assert len(read_jsonl(path)) == 2


# ---------------------------------------------------------------- logger

@pytest.mark.quick
def test_logger_levels(capsys, monkeypatch):
    fflog.set_level("warning")
    fflog.info("invisible %d", 1)
    fflog.warning("visible %d", 2)
    out = capsys.readouterr()
    assert "invisible" not in out.out
    assert "visible 2" in out.err
    fflog.set_level("debug")
    fflog.debug("now shown")
    assert "now shown" in capsys.readouterr().out
    # FF_LOG_LEVEL is read when no explicit level was set
    monkeypatch.setenv("FF_LOG_LEVEL", "error")
    fflog._level = None
    fflog.warning("filtered")
    assert "filtered" not in capsys.readouterr().err
    fflog._level = None
    monkeypatch.delenv("FF_LOG_LEVEL")


@pytest.mark.quick
def test_disabled_telemetry_is_noop(monkeypatch):
    telemetry.deactivate()
    # no session: a span is the profiler's annotation alone (inert while
    # no trace runs) and no Tracer is made or touched
    monkeypatch.setattr(Tracer, "__init__", None)
    monkeypatch.setattr(Tracer, "span", None)
    s1 = telemetry.span("anything", a=1, skipped=[1, 2])
    assert type(s1).__name__ == "TraceAnnotation"
    with s1:
        pass
    telemetry.instant("x")
    telemetry.counter("x", {"v": 1})
    telemetry.event("x", y=2)  # all silently dropped


# ---------------------------------------------------------------- fit e2e

def _build_mlp(tmp_path, extra_argv=()):
    sys.argv = ["test"] + list(extra_argv)
    from flexflow_tpu import (
        ActiMode, FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
    )

    config = FFConfig()
    ff = FFModel(config)
    x = ff.create_tensor((32, 64))
    t = ff.dense(x, 64, ActiMode.AC_MODE_RELU)
    t = ff.dense(t, 10)
    t = ff.softmax(t)
    ff.compile(optimizer=SGDOptimizer(lr=0.1),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.METRICS_ACCURACY])
    return ff


def _train_data(n=256, in_dim=64):
    rs = np.random.RandomState(0)
    return (rs.randn(n, in_dim).astype(np.float32),
            rs.randint(0, 10, (n, 1)).astype(np.int32))


def test_fit_with_telemetry_dir_produces_artifacts(tmp_path):
    """The acceptance scenario: CPU-mesh fit with --telemetry-dir (+
    checkpointing) must yield a loadable Chrome trace with compile/step/
    data-wait/ckpt spans and a JSONL log with the step split + summary."""
    tdir = tmp_path / "telemetry"
    cdir = tmp_path / "ckpt"
    ff = _build_mlp(tmp_path, ["--telemetry-dir", str(tdir),
                               "--checkpoint-dir", str(cdir),
                               "--checkpoint-every", "4"])
    x, y = _train_data()
    ff.fit(x, y, epochs=1, batch_size=32)

    # (a) Chrome trace-event JSON loadable by Perfetto: an object with a
    # traceEvents list whose entries carry name/ph/ts
    trace = json.load(open(tdir / "trace.json"))
    evs = trace["traceEvents"]
    assert isinstance(evs, list)
    for e in evs:
        assert "name" in e and "ph" in e
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
    names = {e["name"] for e in evs}
    for required in ("compile", "step", "data_wait", "ckpt.snapshot",
                     "ckpt.serialize", "ckpt.commit"):
        assert required in names, f"missing span {required!r} in {names}"
    step_spans = [e for e in evs if e["name"] == "step" and e["ph"] == "X"]
    assert len(step_spans) >= 1

    # (b) JSONL: manifest first, step records carry the data-wait /
    # save-latency split, final summary has percentiles + throughput
    recs = read_jsonl(tdir / "metrics.jsonl")
    assert recs[0]["kind"] == "manifest"
    assert recs[0]["mesh_axes"]["data"] == 8
    assert recs[0]["config"]["batch_size"] == 64
    compile_recs = [r for r in recs if r["kind"] == "compile"]
    assert compile_recs and compile_recs[0]["duration_s"] > 0
    steps = [r for r in recs if r["kind"] == "step"]
    assert len(steps) == 8  # 256 samples / batch 32
    for s in steps:
        assert s["data_wait_s"] >= 0
        assert s["save_latency_s"] >= 0
        assert s["step_time_s"] >= s["data_wait_s"]
        assert s["ema_step_time_s"] > 0
    # the policy saved at steps 4 and 8: those steps paid a snapshot
    saves = [r for r in recs if r["kind"] == "checkpoint"]
    assert len(saves) == 2
    for c in saves:
        assert c["bytes"] > 0
        assert c["serialize_s"] >= 0 and c["commit_s"] >= 0
    summary = [r for r in recs if r["kind"] == "summary"][-1]
    assert summary["steps"] == 8
    assert summary["p50_step_time_s"] > 0
    assert summary["p95_step_time_s"] >= summary["p50_step_time_s"]
    assert summary["examples_per_sec"] > 0

    assert ff.get_telemetry() is not None
    telemetry.deactivate()


def test_fit_without_telemetry_leaves_no_session(tmp_path):
    telemetry.deactivate()
    ff = _build_mlp(tmp_path)
    x, y = _train_data(n=64)
    ff.fit(x, y, epochs=1, batch_size=32)
    assert ff.get_telemetry() is None
    assert telemetry.active_session() is None


def test_keras_telemetry_callback(tmp_path):
    sys.argv = ["test"]
    from flexflow_tpu.keras.callbacks import Telemetry
    from flexflow_tpu.keras.layers import Dense, Input
    from flexflow_tpu.keras.models import Model

    tdir = tmp_path / "keras_tel"
    inp = Input(shape=(16,))
    out = Dense(10, activation="softmax")(Dense(32, activation="relu")(inp))
    model = Model(inputs=inp, outputs=out)
    model.compile(optimizer="sgd", loss="sparse_categorical_crossentropy",
                  metrics=["accuracy"])
    rs = np.random.RandomState(0)
    x = rs.randn(128, 16).astype(np.float32)
    y = rs.randint(0, 10, (128, 1)).astype(np.int32)
    model.fit(x, y, epochs=2, callbacks=[Telemetry(str(tdir))])

    recs = read_jsonl(tdir / "metrics.jsonl")
    kinds = {r["kind"] for r in recs}
    assert {"manifest", "step", "keras_epoch", "summary"} <= kinds
    keras_epochs = [r for r in recs if r["kind"] == "keras_epoch"]
    assert [r["epoch"] for r in keras_epochs] == [0, 1]
    assert all("accuracy" in r for r in keras_epochs)
    trace = json.load(open(tdir / "trace.json"))
    assert {"step", "data_wait"} <= {e["name"] for e in trace["traceEvents"]}
    assert model.ffmodel.get_telemetry() is not None
    telemetry.deactivate()


# ---------------------------------------------------------------- profiling

def test_profile_operators_json(tmp_path):
    from flexflow_tpu.profiling import (
        print_operator_profile, profile_operators, profile_operators_json,
    )

    ff = _build_mlp(tmp_path)
    rows = profile_operators(ff.graph)
    recs = profile_operators_json(ff.graph, rows=rows)
    assert recs and set(recs[0]) == {
        "name", "op_type", "forward_s", "backward_s", "total_s"}
    totals = [r["total_s"] for r in recs]
    assert totals == sorted(totals, reverse=True)
    for r in recs:
        assert abs(r["total_s"] - (r["forward_s"] + r["backward_s"])) < 1e-12

    # sorted table goes through the same rows; with a session active the
    # per-op counters land in the trace
    sess = telemetry.activate(
        telemetry.TelemetrySession(str(tmp_path / "prof")))
    import io

    buf = io.StringIO()
    print_operator_profile(ff.graph, file=buf, sort_by_total=True)
    assert "TOTAL" in buf.getvalue()
    counters = [e for e in sess.tracer.to_dict()["traceEvents"]
                if e["ph"] == "C" and e["name"].startswith("op_profile.")]
    assert counters
    telemetry.deactivate()
