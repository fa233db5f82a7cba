"""The readers of the program's start-up record (benchmarks/startup.py and
the six `setup_*` files under layer_metrics/), on records made by hand:
each metric, the cut at the run's start and at the window's opening, union
on a thread against sum over threads, self time, a program with no record
(every parent of PR 51) and a record with nothing in it.
"""

import threading
import types

import pytest

from benchmarks import harness
from benchmarks import startup as helper

MAIN = threading.main_thread().ident
AHEAD = MAIN + 1        # a compile-ahead thread
METRICS = ("setup_import_s", "setup_search_s", "setup_weights_s",
           "setup_trace_s", "setup_programs", "setup_unnamed_s")
T_START, OPENED, CLOSED = 100.0, 200.0, 210.0


def ev(name, t0, t1, tid=MAIN, **args):
    return (name, t0, t1, tid, args)


def build(kind, program, t0, t1, tid=MAIN, **args):
    return ev("build." + kind, t0, t1, tid, program=program, **args)


# A start by hand. Main thread: the import (with one eager build inside),
# a training compile, a serve() whose decode graph compiles under
# serve.graph, a step program built in the first round, and one that
# straddles the window's opening. A second thread compiles ahead.
RECORD = [
    build("backend", "iota", 100.5, 100.7),
    ev("import", 100.0, 102.0),
    ev("compile.graph", 110.0, 110.5),
    ev("warmstart.plan_lookup", 110.5, 110.6, layer="cache"),
    ev("compile.calibrate", 110.6, 111.0),
    ev("compile.search", 111.0, 112.0, mode="joint"),
    ev("compile.update_sharding", 112.0, 112.1),
    ev("compile.executor", 112.1, 112.2),
    ev("compile.verify", 112.2, 112.7),
    build("trace", "normal", 113.0, 113.4),
    build("trace", "_normal_real", 113.1, 113.3),     # nested in normal's
    build("lower", "normal", 113.4, 113.5),
    build("backend", "normal", 113.5, 114.5, cache_read_s=0.9),
    ev("compile.init", 112.7, 116.0),
    ev("compile", 110.0, 117.0, comp_mode="training"),
    ev("compile.graph", 120.0, 120.2),
    ev("compile.verify", 120.2, 120.4),
    ev("compile.init", 120.4, 121.0),
    ev("compile", 120.0, 121.5, comp_mode="inference"),
    ev("serve.graph", 120.0, 122.0),
    ev("serve.adopt", 122.0, 124.0),
    ev("serve.step_fn", 124.0, 124.1),
    ev("serve.pool", 124.1, 124.5),
    ev("serve.compile", 120.0, 125.0, slots=8),
    build("trace", "decode_step", 130.0, 134.0, AHEAD),
    build("lower", "decode_step", 134.0, 135.0, AHEAD),
    build("backend", "decode_step", 135.0, 140.0, AHEAD),
    build("trace", "decode_step", 150.0, 152.0),
    build("lower", "decode_step", 152.0, 153.0),
    build("backend", "decode_step", 153.0, 156.0),
    build("trace", "keep", 199.0, 199.5),
    build("backend", "keep", 199.5, 203.0),           # straddles the opening
    build("backend", "late", 205.0, 206.0),           # inside the window
    build("backend", "after", 220.0, 221.0),          # after it closed
]
# by hand, from the table above
EXPECTED = {
    "setup_import_s": 2.0,
    # plan_lookup 0.1 + calibrate 0.4 + search 1.0 + update_sharding 0.1
    # + verify 0.5, and the decode graph's verify 0.2
    "setup_search_s": 2.3,
    # compile.init 3.3 and 0.6, serve.adopt 2.0
    "setup_weights_s": 5.9,
    # main: normal 0.4 + 0.1 (the nested trace inside it once), decode_step
    # 2 + 1, keep 0.5; the other thread 4 + 1
    "setup_trace_s": 9.0,
    # iota, normal, decode_step twice; keep ended inside the window
    "setup_programs": 4.0,
    # compile 7.0 less its children's 6.0; the nested compile 1.5 less 1.0;
    # serve.compile 5.0 less 4.5
    "setup_unnamed_s": 1.0 + 0.5 + 0.5,
}


class Side:
    """What flexflow_tpu.telemetry.startup hands a reader."""

    def __init__(self, events, dropped=0):
        self._events, self._dropped = list(events), dropped

    def events(self):
        return list(self._events)

    def dropped(self):
        return self._dropped


def a_run(spans=()):
    ctx = types.SimpleNamespace(
        t_start=T_START, window=(OPENED, CLOSED), spans=list(spans),
        xla_compile_setup_s=10.2,
        seconds_in=lambda name: [b - a for n, a, b in spans if n == name])
    return types.SimpleNamespace(ctx=ctx)


@pytest.fixture
def by_hand(monkeypatch):
    monkeypatch.setattr(helper, "read_side", lambda: Side(RECORD, 3))


@pytest.mark.parametrize("metric", METRICS)
def test_each_metric_by_hand(by_hand, metric):
    value = harness.load_reader(metric).read(a_run())
    assert isinstance(value, float)
    assert value == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", METRICS)
def test_a_program_with_no_record_reads_none(monkeypatch, metric):
    from flexflow_tpu import telemetry

    monkeypatch.delattr(telemetry, "startup")     # a parent of PR 51
    assert helper.read_side() is None
    assert harness.load_reader(metric).read(a_run()) is None


@pytest.mark.parametrize("metric", METRICS)
def test_an_empty_record_reads_a_number(monkeypatch, capsys, metric):
    monkeypatch.setattr(helper, "read_side", lambda: Side([]))
    assert harness.load_reader(metric).read(a_run()) == 0.0
    assert "0 events, 0 dropped" in capsys.readouterr().out


def test_the_read_side_is_the_programs(monkeypatch):
    from flexflow_tpu.telemetry import startup

    assert helper.read_side() is startup
    # and the two keep the same arithmetic, each its own copy
    intervals = [(0.0, 9.0), (1.0, 4.0), (2.0, 3.0), (3.5, 8.0), (8.5, 9.5)]
    assert helper.self_seconds(intervals) == startup.self_seconds(intervals)
    assert helper.union_seconds(intervals) == startup.union_seconds(intervals)
    assert (helper.TRACE, helper.LOWER, helper.BACKEND) == (
        startup.TRACE, startup.LOWER, startup.BACKEND)


def test_the_record_is_read_once_a_run(by_hand, capsys):
    run = a_run()
    first = helper.record(run)
    assert helper.record(run) is first and first.dropped == 3
    assert capsys.readouterr().out.count("the program's record of") == 1


def test_the_cut_counts_the_part_inside():
    rec = helper.read(Side([
        build("trace", "early", 98.0, 101.0),      # straddles the start
        build("backend", "keep", 199.5, 203.0),    # straddles the opening
        build("trace", "keep", 199.0, 199.5),
        build("trace", "never", 90.0, 99.0),       # before the run began
        ev("import", 99.0, 102.0),
    ]), T_START, OPENED, MAIN, CLOSED)
    assert sorted(e[4]["program"] for e in rec.events if e[4]) == [
        "early", "keep", "keep"]
    assert helper.trace_s(rec) == pytest.approx(1.0 + 0.5)
    assert helper.import_s(rec) == pytest.approx(2.0)
    # a build that ends inside the window is no program of set-up, and
    # is what the window's compilations are called
    assert helper.programs(rec) == 0.0
    assert [e[4]["program"] for e in rec.late] == ["keep"]


def test_union_on_a_thread_and_sum_over_threads():
    same = [build("trace", "outer", 110.0, 114.0),
            build("trace", "inner", 111.0, 112.0),
            build("lower", "outer", 114.0, 115.0)]
    one = helper.read(Side(same), T_START, OPENED, MAIN)
    assert helper.trace_s(one) == pytest.approx(5.0)     # not 6
    two = helper.read(Side(
        same + [build("trace", "outer", 110.0, 114.0, AHEAD)]),
        T_START, OPENED, MAIN)
    assert helper.trace_s(two) == pytest.approx(9.0)     # the threads add
    # planning is the job's thread's alone
    planned = helper.read(Side([
        ev("compile.search", 110.0, 112.0),
        ev("compile.search", 110.0, 112.0, AHEAD)]), T_START, OPENED, MAIN)
    assert helper.search_s(planned) == pytest.approx(2.0)


def test_self_time_with_a_child_and_a_build_inside(by_hand):
    rec = helper.record(a_run())
    found = helper.inside_of(rec, 110.0, 117.0)      # the training compile
    assert found["phases"]["compile"] == pytest.approx(1.0)
    # compile.init 3.3, less normal's trace, lower and backend 1.5
    assert found["phases"]["compile.init"] == pytest.approx(1.8)
    assert found["phases"]["compile.search"] == pytest.approx(1.0)
    # a trace's own seconds leave out the trace nested in it
    assert found["builds"] == pytest.approx({
        helper.TRACE: 0.4, helper.LOWER: 0.1, helper.BACKEND: 1.0})
    assert found["left"] == pytest.approx(0.0)
    assert sum(found["others"].values()) == 0.0
    # the other thread's builds are told apart, and the job's thread is
    # idle under them
    ahead = helper.inside_of(rec, 130.0, 140.0)
    assert ahead["others"] == pytest.approx({
        helper.TRACE: 4.0, helper.LOWER: 1.0, helper.BACKEND: 5.0})
    assert ahead["left"] == pytest.approx(10.0) and not ahead["phases"]


def test_the_roots_once_and_the_programs_by_cost(by_hand):
    rec = helper.record(a_run())
    # compile 7, serve.compile 5 with the decode graph's compile inside it
    assert helper.root_seconds(rec) == pytest.approx(12.0)
    top = helper.by_program(rec.events)
    assert [(p, n) for _, n, p in top[:2]] == [("decode_step", 2),
                                               ("normal", 1)]
    assert top[0][0] == pytest.approx(16.0)


def test_the_table_of_a_traced_run(by_hand, capsys):
    spans = [("ffcompile", 105.0, 118.0), ("ffcompile", 119.0, 126.0),
             ("lower_ahead", 128.0, 129.0), ("first_round", 145.0, 199.9),
             ("submit", 146.0, 146.1), ("engine_step", 150.0, 156.5),
             ("fit", 201.0, 205.0)]
    run = a_run(spans)
    assert [s[0] for s in helper.setup_spans(run.ctx, T_START, OPENED)] == [
        "(before)", "ffcompile", "ffcompile", "lower_ahead", "first_round"]
    helper.record(run)
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("[setup] the program's record of 100.00 s")
    assert "3 dropped" in out[0]
    before = next(line for line in out if "(before)" in line)
    assert "import 1.80" in before and "backend 0.20" in before
    first = next(line for line in out if "[setup] ffcompile 13.00" in line)
    assert "compile.init 1.80" in first and "left 6.00" in first
    assert any("decode_step: 16.00 build s, 2 builds" in line
               for line in out)
    check = next(line for line in out if "harness's listener" in line)
    # iota 0.2, normal 1.0, decode_step 5 + 3: the straddler is not heard
    assert "build.backend 9.200 s in 4 programs (1 read" in check
    assert "heard 10.200" in check
    roots = next(line for line in out if "of ffcompile's" in line)
    assert "12.00 s of ffcompile's 20.00: 8.00 s left" in roots
    assert "compile.init 3.90, serve.adopt 2.00" in roots
    assert "built inside the window: keep" in out[-2]
    assert "built inside the window: late" in out[-1]
