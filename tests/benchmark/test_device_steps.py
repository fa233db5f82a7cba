"""benchmarks/device_steps.py and the six per-layer metrics of PR 35 on
profiles made by hand: the join of a step's `ff/serve.dispatch` span to
its execution on the device's `XLA Modules` line, by order and checked
by causality, with one step in flight, a step completed at once, the
last step of a drain and steps the window's ends cut; a join at fault;
and nothing to read where the spans carry no `step` or the trace has no
such line.
"""

import pytest

from benchmarks import device_steps, harness

from test_program_spans import plane_text, run_over

WINDOW = (50, 10000)
PROGRAM = "jit_decode_step"
NEW_METRICS = ("device_step_ms.decode.serve", "device_step_ms.chunk.serve",
               "chunk_share_pct.serve", "host_iter_ms.serve",
               "host_stage_ms.serve", "step_join_pct.serve")


def tag_of(tagged, step, **more):
    return dict(more, step=step) if tagged else more


def iteration(at, length, number, dispatch=None, fetches=(), step_span=None,
              tagged=True):
    """The `ff/` spans of one engine iteration [at, at + length): what it
    schedules, stages and dispatches at fixed offsets, `dispatch` being
    the arguments of `serve.dispatch`; its fetches as (step, start, end,
    ahead); `step_span`, (name, arguments), over the call up to its last
    fetch. `tagged` False: a program from before the step ids."""
    spans = [("ff/serve.iteration", at, at + length, {"iteration": number})]

    def tag(step, **more):
        return tag_of(tagged, step, **more)

    if step_span is not None:
        name, args = step_span
        spans.append((name, at + 5, max(f[2] for f in fetches), args))
    if dispatch is not None:
        of = dispatch["step"]
        spans += [
            ("ff/serve.schedule", at + 10, at + 50, tag(of)),
            ("ff/serve.stage", at + 60, at + 260, tag(of)),
            ("ff/serve.stage", at + 70, at + 130, tag(of, part="build")),
            ("ff/serve.stage", at + 130, at + 190, tag(of, part="put")),
            ("ff/serve.stage", at + 200, at + 240, tag(of, part="feed")),
            ("ff/serve.stage", at + 245, at + 255, tag(of, part="put")),
            ("ff/serve.dispatch", at + 270, at + 370,
             dict(dispatch, program=PROGRAM) if tagged else {}),
            ("ff/serve.advance", at + 375, at + 385, tag(of)),
        ]
    for step, start, end, ahead in fetches:
        spans.append(("ff/serve.fetch", start, end, tag(step, ahead=ahead)))
        spans.append(("ff/serve.bookkeep", end + 5, end + 25, tag(step)))
    return spans


def decode(step):
    return {"step": step, "kind": "decode", "rows": 4}


def chunk(step, bucket, start):
    return {"step": step, "kind": "chunk", "rows": 4 + bucket,
            "bucket": bucket, "chunk_start": start}


def host_spans(tagged=True, fetch_11_closes=8150):
    """Step 6 was dispatched before the trace began; steps 7-11 are each
    dispatched by one call and fetched by the next (one step in flight);
    the call after 11's finds no row to run and fetches it with nothing
    behind it (the last step of a drain); step 12 is completed at once;
    step 13 is dispatched inside the window and fetched after it."""
    def it(*args):
        return iteration(*args, tagged=tagged)

    return [("bench/window", *WINDOW, {})] + [
        *it(100, 800, 1, decode(7), [(6, 480, 850, 1)]),
        *it(1000, 900, 2, chunk(8, 8, 16), [(7, 1390, 1450, 1)]),
        *it(2000, 1700, 3, decode(9), [(8, 2390, 3560, 1)],
            ("ff/serve.prefill", tag_of(tagged, 8, kv_rows=77))),
        *it(3800, 1000, 4, chunk(10, 4, 24), [(9, 4190, 4650, 1)]),
        *it(4900, 2200, 5, decode(11), [(10, 5290, 7050, 1)]),
        *it(7200, 1000, 6, None, [(11, 7210, fetch_11_closes, 0)]),
        *it(8300, 1500, 7, decode(12), [(12, 8690, 9750, 0)]),
        *it(9500, 450, 8, decode(13)),
    ]


# the executions of the device's `XLA Modules` line: the decode program
# is fingerprint 11, the chunk programs 22 (bucket 8) and 33 (bucket 4)
MODULES = {
    6: (f"{PROGRAM}(11)", 0, 300),
    "feed": ("jit_feed(1)", 400, 420),
    7: (f"{PROGRAM}(11)", 500, 1400),
    "keep": ("jit_keep(2)", 1410, 1420),
    8: (f"{PROGRAM}(22)", 1500, 3500),
    9: (f"{PROGRAM}(11)", 3600, 4600),
    10: (f"{PROGRAM}(33)", 4700, 7000),
    11: (f"{PROGRAM}(11)", 7100, 8100),
    12: (f"{PROGRAM}(11)", 8700, 9700),
    13: (f"{PROGRAM}(11)", 9900, 10900),
}
# an operation over each execution; step 8's leaves 2,500-2,600 idle
OPS = [("%fusion.1 = bf16[4] fusion(x)", a, b)
       for _, a, b in MODULES.values() if (a, b) != (1500, 3500)] + [
    ("%fusion.2 = bf16[4] fusion(x)", 1500, 2500),
    ("%fusion.3 = bf16[4] fusion(x)", 2600, 3500)]


def profile(spans, modules=MODULES, modules_line=True, later=0) -> str:
    """`later`: nanoseconds the device's clock is ahead of the host's."""
    def on_the_device(events):
        return sorted(((name, a + later, b + later) for name, a, b in events),
                      key=lambda e: e[1])

    lines = [("XLA Ops", on_the_device(OPS)), ("Async XLA Ops", [])]
    if modules_line:
        lines.append(("XLA Modules", on_the_device(modules.values())))
    return plane_text(1, "/device:TPU:0", lines) + "\n" + plane_text(
        2, "/host:CPU", [("python", spans)])


def read_all(run) -> dict:
    return {m: harness.load_reader(m).read(run) for m in NEW_METRICS}


def test_an_exact_join_and_each_metric_by_hand(tmp_path, capsys):
    run = run_over(profile(host_spans()), tmp_path)
    steps = device_steps.steps(run)
    assert [(s.id, s.kind, s.bucket, s.chunk_start, s.rows, s.start, s.end)
            for s in steps] == [
        (7, "decode", 0, 0, 4, 500, 1400),
        (8, "chunk", 8, 16, 12, 1500, 3500),
        (9, "decode", 0, 0, 4, 3600, 4600),
        (10, "chunk", 4, 24, 8, 4700, 7000),
        (11, "decode", 0, 0, 4, 7100, 8100),    # fetched with `ahead` 0
        (12, "decode", 0, 0, 4, 8700, 9700)]    # completed at once
    assert [s.busy_ns for s in steps] == [900, 1900, 1000, 2300, 1000, 1000]
    # idle since the step before ended: 300-500 less the feed's 20,
    # 1,400-1,500 less the keep's 10, then whole
    assert [s.idle_before_ns for s in steps] == [180, 90, 100, 100, 100, 600]
    # a step's arguments: its dispatch span's and its step span's
    assert steps[1].args["kv_rows"] == 77 and steps[1].args["program"] == (
        PROGRAM)
    found = device_steps.record(run)
    # steps 6 and 13 are cut by the window's ends: not dispatched and
    # fetched inside it, and their executions' busy time is `cut`
    assert found.dispatched == 6
    assert found.busy_ns == {"steps": 8100, "cut": 250 + 100, "between": 30}
    assert sum(found.busy_ns.values()) == found.window_busy_ns == (
        pytest.approx(run.trace.busy_s(0) * 1e9))
    values = read_all(run)
    assert values["step_join_pct.serve"] == 100.0
    # decode steps of 900, 1000, 1000, 1000 ns; chunk steps of 2000, 2300
    assert values["device_step_ms.decode.serve"] == pytest.approx(1000e-6)
    assert values["device_step_ms.chunk.serve"] == pytest.approx(2150e-6)
    assert values["chunk_share_pct.serve"] == pytest.approx(
        100 * 4300 / (4300 + 3900))
    # the iterations that dispatched a decode step, less their fetches:
    # 800 - 370, 1700 - 1170, 2200 - 1760, 1500 - 1060
    assert values["host_iter_ms.serve"] == pytest.approx(440e-6)
    # every staging iteration spends 200 ns in serve.stage: 60 building,
    # 60 + 10 putting, 40 in the feed, 30 of its own
    assert values["host_stage_ms.serve"] == pytest.approx(200e-6)
    out = capsys.readouterr().out
    assert "6 steps joined to a device interval of 6 dispatched" in out
    assert ("chunk bucket 8: median 0.002, 90th percentile 0.002, 1 steps; "
            "idle before one, mean 0.000") in out
    assert "build 0.000, put 0.000, feed 0.000" in out
    # every pairing is causal as the clocks stand, and under any offset
    # from -130 (step 7 starts 130 after its dispatch opens) to 50 (its
    # fetch closes 50 after it ends)
    assert found.clock_ns == (-130, 50)
    # the longest gap, 8,100-8,700, ends in step 12; at its middle less
    # 40 the host was staging that step
    gap = found.gaps[0]
    assert (gap.ns, gap.at_ns, gap.then.id, gap.host, gap.host_step) == (
        600, 8100 - WINDOW[0], 12, "ff/serve.stage", 12)
    assert len(found.gaps) == 10
    assert "idle 0.001 ms at 0.0 ms, before step 12 decode; the host in " \
           "ff/serve.stage of step 12" in out


def test_clocks_that_differ_by_one_offset_join_as_well(tmp_path):
    """The device's clock 300 ns behind the host's: step 12 now starts
    170 *before* its dispatch span opens as the trace has them, and
    every pairing is causal under the offsets 170 to 350."""
    run = run_over(profile(host_spans(), later=-300), tmp_path)
    found = device_steps.record(run)
    assert len(found.steps) == found.dispatched == 6
    assert found.clock_ns == (170, 350)
    assert found.steps[-1].start < 8570 < found.steps[-1].end
    # the gap before step 12 is 7,800-8,400 on the device's clock, its
    # middle 8,360 on the host's: the same span as with clocks that agree
    assert (found.gaps[0].host, found.gaps[0].host_step) == (
        "ff/serve.stage", 12)
    assert harness.load_reader("step_join_pct.serve").read(run) == 100.0


def test_the_most_values_that_intervals_share():
    assert device_steps.clock_offset(
        [(0, 10), (5, 20), (8, 9), (30, 40)]) == (8, 9)
    assert device_steps.clock_offset([(0, 1), (1, 2)]) == (1, 1)
    assert device_steps.clock_offset([]) == (0.0, 0.0)


def test_the_record_is_read_once(tmp_path, capsys):
    run = run_over(profile(host_spans()), tmp_path)
    assert device_steps.record(run) is device_steps.record(run)
    assert capsys.readouterr().out.count("steps joined") == 1


@pytest.mark.parametrize("fault", ["never ran", "ends after its fetch",
                                   "starts before its dispatch",
                                   "a program of two shapes"])
def test_a_join_at_fault_reads_under_100_and_silences_the_rest(
        fault, tmp_path):
    modules, spans = dict(MODULES), host_spans()
    if fault == "never ran":
        # step 12's execution is missing: step 13's takes its place and
        # ends long after step 12's fetch
        del modules[12]
    elif fault == "ends after its fetch":
        # step 11 ends at 8,100: 200 after its fetch, more than the 130
        # that any other pairing lets the clocks differ by
        spans = host_spans(fetch_11_closes=7900)
    elif fault == "starts before its dispatch":
        # dispatched at 8,570, seen on the device from 8,400
        modules[12] = (f"{PROGRAM}(11)", 8400, 9700)
    else:
        # the chunk program of bucket 8 under a decode step's span
        modules[9] = (f"{PROGRAM}(22)", 3600, 4600)
    values = read_all(run_over(profile(spans, modules), tmp_path))
    assert values.pop("step_join_pct.serve") == pytest.approx(100 * 5 / 6)
    assert set(values.values()) == {None}


def test_nothing_to_read_without_the_line_or_the_ids(tmp_path):
    without_line = run_over(profile(host_spans(), modules_line=False),
                            tmp_path / "line")
    assert device_steps.steps(without_line) is None
    assert set(read_all(without_line).values()) == {None}
    # a parent commit's spans: the same names, no `step`, no `program`
    parent = run_over(profile(host_spans(tagged=False)), tmp_path / "ids")
    assert device_steps.record(parent) is None
    assert set(read_all(parent).values()) == {None}
    # and a program without spans at all
    bare = run_over(profile(host_spans()[:1]), tmp_path / "bare")
    assert set(read_all(bare).values()) == {None}


def test_spans_of_another_trace_are_not_joined(tmp_path):
    run = run_over(profile(host_spans()), tmp_path)
    run.trace.window = (50.0, 9000.0)
    assert device_steps.record(run) is None


def test_busy_inside_by_hand():
    busy = [(0, 10), (20, 30), (40, 50)]
    assert device_steps.busy_inside(
        busy, [(0, 50), (5, 25), (10, 20), (12, 18), (25, 100), (60, 70)]
    ) == [30, 10, 0, 0, 15, 0]
    assert device_steps.busy_inside([], [(0, 5)]) == [0]
