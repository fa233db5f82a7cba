"""The readers of the program's own spans (benchmarks/program_spans.py and
the seven per-layer metrics of PR 25), on the CPU: each reader's arithmetic
on a profile made by hand, the same readers on a recorded TPU trace of
three engine iterations, and nothing to read where a trace holds no `ff/`
span (a parent commit from before the spans).
"""

import json
import os
import types

import jax
import pytest

from benchmarks import harness, program_spans, trace

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = {"n_layer": 2, "n_embd": 32}
PEAKS = {"hbm_bytes_per_s": 1e12}

# One pure-decode iteration and one with a four-token prefill chunk, in
# nanoseconds: (name, start, end, arguments).
SERVE_SPANS = [
    ("bench/window", 0, 1000, {}),
    ("ff/serve.iteration", 0, 480, {"iteration": 1}),
    ("ff/serve.schedule", 0, 40, {}),
    ("ff/serve.prepare_writes", 40, 80, {}),
    ("ff/serve.cow_copy", 50, 70, {"blocks": 1}),
    ("ff/serve.step", 80, 440, {"active": 2, "kv_rows": 100,
                                "kv_itemsize": 2}),
    ("ff/serve.stage", 80, 120, {}),
    ("ff/serve.dispatch", 120, 160, {}),
    ("ff/serve.fetch", 160, 440, {}),
    ("ff/serve.bookkeep", 440, 480, {}),
    ("ff/serve.iteration", 500, 980, {"iteration": 2}),
    ("ff/serve.schedule", 500, 540, {}),
    ("ff/serve.prepare_writes", 540, 560, {}),
    ("ff/serve.prefill", 560, 940, {"trace": "req-1", "tokens": 4,
                                    "kv_rows": 50, "kv_itemsize": 2}),
    ("ff/serve.stage", 560, 600, {}),
    ("ff/serve.dispatch", 600, 640, {}),
    ("ff/serve.fetch", 640, 940, {}),
    ("ff/serve.bookkeep", 940, 980, {}),
]
SERVE_OPS = [
    ("%copy.1 = f32[16] copy(x)", 60, 65),
    ("%fusion.1 = bf16[4] fusion(x)", 150, 200),
    ("%flash_attention_paged_decode.1 = bf16[4] custom-call(q)", 200, 300),
    ("%fusion.2 = bf16[4] fusion(x)", 300, 330),
    ("%flash_attention_paged_decode.2 = bf16[4] custom-call(q)", 330, 420),
    ("%fusion.3 = bf16[4] fusion(x)", 630, 900),
]
# Chip 0 is idle 0-60, 65-150, 420-630 and 900-1000: 455 ns, which the
# innermost spans over it share out as
SERVE_IDLE_NS = {
    "ff/serve.schedule": 40 + 40, "ff/serve.bookkeep": 40 + 40,
    "ff/serve.prepare_writes": 10 + 10 + 20, "ff/serve.cow_copy": 10 + 5,
    "ff/serve.stage": 40 + 40, "ff/serve.dispatch": 30 + 30,
    "ff/serve.fetch": 20 + 40, "outside": 20 + 20}

# Two steps of a fit call and its drain; a collective is open 250-500 and
# other operations run 200-300 and 400-450 of it.
TRAIN_SPANS = [
    ("bench/window", 0, 1000, {}),
    ("ff/fit", 50, 960, {"steps": 2, "batch_size": 8}),
    ("ff/step", 100, 400, {"step": 1}),
    ("ff/data_wait", 100, 130, {}),
    ("ff/step", 400, 700, {"step": 2}),
    ("ff/data_wait", 400, 420, {}),
    ("ff/fit.drain", 700, 950, {}),
]
TRAIN_OPS = [
    ("%fusion.1 = bf16[4] fusion(x)", 200, 300),
    ("%all-reduce.1 = bf16[4] all-reduce(x)", 300, 350),
    ("%fusion.2 = bf16[4] fusion(x)", 400, 450),
    ("%collective-permute-done.1 = bf16[4] collective-permute-done(x)",
     450, 480),
]
TRAIN_ASYNC = [("%all-gather-start.1 = bf16[4] all-gather-start(x)",
                250, 500)]

# reader -> (the profile it reads, its value by hand)
BY_HAND = {
    "engine_idle_ms.schedule": ("serve", (80 + 80) / 2 * 1e-6),
    "engine_idle_ms.stage": ("serve", (40 + 15 + 80) / 2 * 1e-6),
    "engine_idle_ms.fetch": ("serve", (60 + 60) / 2 * 1e-6),
    # one iteration ran the kernel: 100 + 90 ns
    "paged_decode_ms.serve": ("serve", 190 * 1e-6),
    # its 100 rows x 2 layers x (K, V) x 32 wide x 2 bytes = 25.6 kB take
    # 25.6 ns at 1e12 B/s
    "paged_decode_roofline_pct.serve": ("serve", 100 * 25.6 / 190),
    # open 250-500, alone 300-400 and 450-500, over two steps
    "collective_exposed_ms.train": ("train", (100 + 50) / 2 * 1e-6),
    "input_wait_ms.train": ("train", (30 + 20) / 2 * 1e-6),
}


def plane_text(plane_id, name, lines) -> str:
    """An XPlane as text: lines of (name, start_ns, end_ns[, stats])."""
    names, stat_names, out = {}, {}, [
        f'planes {{ id: {plane_id} name: "{name}"']
    for line_id, (line_name, events) in enumerate(lines, 1):
        out.append(f'lines {{ id: {line_id} name: "{line_name}"')
        for event_name, start, end, *rest in events:
            stats = ""
            for key, value in (rest[0] if rest else {}).items():
                kind = "str_value" if isinstance(value, str) else (
                    "int64_value")
                stats += (f" stats {{ metadata_id: "
                          f"{stat_names.setdefault(key, len(stat_names) + 1)}"
                          f" {kind}: {json.dumps(value)} }}")
            out.append(
                f"events {{ metadata_id: "
                f"{names.setdefault(event_name, len(names) + 1)} offset_ps: "
                f"{start * 1000} duration_ps: {(end - start) * 1000}{stats} }}")
        out.append("}")
    for table, key in ((names, "event_metadata"), (stat_names,
                                                    "stat_metadata")):
        out += [f"{key} {{ key: {i} value {{ id: {i} name: "
                f"{json.dumps(n)} }} }}" for n, i in table.items()]
    return "\n".join(out + ["}"])


def profile_text(ops, async_ops, spans) -> str:
    return plane_text(1, "/device:TPU:0", [
        ("XLA Ops", ops), ("Async XLA Ops", async_ops)]) + "\n" + plane_text(
            2, "/host:CPU", [("python", spans)])


PROFILES = {"serve": profile_text(SERVE_OPS, [], SERVE_SPANS),
            "train": profile_text(TRAIN_OPS, TRAIN_ASYNC, TRAIN_SPANS)}


def run_over(text, trace_dir) -> types.SimpleNamespace:
    """What run.py hands a reader, for a profile written under trace_dir
    as the profiler would have."""
    where = trace_dir / "plugins" / "profile" / "recorded"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    return types.SimpleNamespace(
        trace=trace.read_file(trace.newest_xplane(str(trace_dir))),
        ctx=types.SimpleNamespace(trace_dir=str(trace_dir)),
        config=CONFIG, peaks=PEAKS, result={"counters": {}})


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_a_readers_arithmetic_by_hand_and_nothing_without_spans(
        metric, tmp_path):
    which, by_hand = BY_HAND[metric]
    reader = harness.load_reader(metric)
    run = run_over(PROFILES[which], tmp_path / "with")
    assert reader.read(run) == pytest.approx(by_hand, rel=1e-9)
    # the same device events under a program that has no spans
    spans = {"serve": SERVE_SPANS, "train": TRAIN_SPANS}[which]
    ops, async_ops = {"serve": (SERVE_OPS, []),
                      "train": (TRAIN_OPS, TRAIN_ASYNC)}[which]
    bare = run_over(profile_text(ops, async_ops, spans[:1]),
                    tmp_path / "without")
    assert program_spans.spans(bare) == []
    assert reader.read(bare) is None


def test_idle_is_shared_out_among_the_innermost_spans(tmp_path):
    run = run_over(PROFILES["serve"], tmp_path)
    by = program_spans.idle_by_span(run)
    assert {k: round(v * 1e9) for k, v in by.items() if v} == SERVE_IDLE_NS
    idle_s = run.trace.window_s * run.trace.idle_pct(0) / 100
    assert sum(by.values()) == pytest.approx(idle_s) == pytest.approx(455e-9)
    assert program_spans.idle_under(
        run, ("ff/serve.stage", "ff/serve.cow_copy")) == pytest.approx(95e-9)
    assert program_spans.count(run, "ff/serve.iteration") == 2
    assert program_spans.seconds_in(
        run, ("ff/serve.iteration", "ff/serve.stage")) == pytest.approx(
            960e-9)                       # overlap counts once
    # the four-token chunk took the path without the kernel
    assert [s[0] for s in program_spans.decode_kernel_steps(run)] == [
        "ff/serve.step"]
    one_token = [(n, a, b, dict(args, tokens=1) if "tokens" in args else args)
                 for n, a, b, args in SERVE_SPANS]
    assert len(program_spans.decode_kernel_steps(run_over(
        profile_text(SERVE_OPS, [], one_token), tmp_path / "q1"))) == 2


def test_interval_overlap_and_exposed_seconds(tmp_path):
    assert program_spans.overlap([(0, 10), (20, 30)], [(5, 25), (28, 40)]) == [
        (5, 10), (20, 25), (28, 30)]
    assert program_spans.overlap([(0, 10)], []) == []
    run = run_over(PROFILES["train"], tmp_path)

    def collective(name):
        return bool(trace.COLLECTIVE.match(name))

    assert program_spans.device_seconds_while(
        run, collective) == pytest.approx(250e-9)
    assert program_spans.device_seconds_while(
        run, collective, alone=True) == pytest.approx(150e-9)
    # what PERF.md reports in prose for the training cells: idle under
    # ff/fit outside any step (the call's start), and under the drain
    by = program_spans.idle_by_span(run)
    assert by["ff/fit"] == pytest.approx((50 + 10) * 1e-9)
    assert by["ff/fit.drain"] == pytest.approx(250e-9)


def test_spans_of_another_trace_are_not_read(tmp_path):
    """Spans are held against device events only if both are of one
    trace: the file's own bench/window span has to be the window of
    run.trace (tests steer run.trace to a recorded file while the
    directory holds the CPU run's)."""
    run = run_over(PROFILES["serve"], tmp_path)
    assert len(program_spans.spans(run)) == len(SERVE_SPANS) - 1
    run = run_over(PROFILES["serve"], tmp_path / "other")
    run.trace.window = (0.0, 900.0)
    assert program_spans.spans(run) == []


@pytest.fixture(scope="module")
def recorded():
    """recorded_serve_trace.textproto: chip 0 and the host plane of three
    engine iterations of `c13b-serve-chat` on a v5e (PR 25's chip run):
    one that carries a 12-token prefill chunk, one with a copy-on-write
    copy, one plain decode step. Cut from the traced window by hand:
    device events under 100 ns (three quarters of them, 19 us together)
    and the async line are left out, instruction texts are cut short,
    times are rebased, and the window span is cut to the three
    iterations."""
    with open(os.path.join(HERE, "recorded_serve_trace.textproto")) as f:
        profile = jax.profiler.ProfileData.from_text_proto(f.read())
    t = trace.read(profile)
    return types.SimpleNamespace(
        trace=t, program_spans=program_spans.read(profile, t.window),
        config=harness.load_json("configs", "cerebras-gpt-1.3b.json"),
        peaks=harness.load_json("peaks.json")["TPU v5 lite"],
        result={"counters": {}})


# what each serving reader gives on the recorded iterations
RECORDED = {
    "engine_idle_ms.schedule": 0.19417,
    "engine_idle_ms.stage": 3.00744,
    "engine_idle_ms.fetch": 2.35546,
    # 81.14 ms of kernel events over the two pure-decode iterations
    "paged_decode_ms.serve": 40.57029,
    # their 3,365 + 3,381 context rows x 24 layers x (K, V) x 2,048 wide
    # x 2 bytes = 1.326 GB: 1.619 ms at 819 GB/s
    "paged_decode_roofline_pct.serve": 100 * 1.61944 / 81.14058,
}


@pytest.mark.parametrize("metric", sorted(RECORDED))
def test_a_reader_on_a_recorded_tpu_trace(metric, recorded):
    assert harness.load_reader(metric).read(recorded) == pytest.approx(
        RECORDED[metric], rel=1e-4)


def test_the_recorded_iterations_add_up(recorded):
    spans = recorded.program_spans
    calls = program_spans.named(recorded, "ff/serve.prefill",
                                "ff/serve.step")
    assert [(c[0], c[3]["kv_rows"], c[3]["kv_itemsize"]) for c in calls] == [
        ("ff/serve.prefill", 3349, 2), ("ff/serve.step", 3365, 2),
        ("ff/serve.step", 3381, 2)]
    assert calls[0][3]["tokens"] == 12 and calls[0][3]["trace"] == "req-36"
    assert len(program_spans.decode_kernel_steps(recorded)) == 2
    assert {s[0] for s in spans} == {
        "ff/serve." + n for n in (
            "iteration", "schedule", "prepare_writes", "cow_copy", "prefill",
            "step", "stage", "dispatch", "fetch", "bookkeep")}
    # every idle second is under exactly one label
    by = program_spans.idle_by_span(recorded)
    idle_s = recorded.trace.window_s - recorded.trace.busy_s(0)
    assert sum(by.values()) == pytest.approx(idle_s) == pytest.approx(
        0.017257, rel=1e-3)
    # the three phase groups hold all but the glue between the spans of an
    # iteration (under 4 %) and what lies outside the iterations
    inside_ms = (idle_s - by["outside"]) * 1e3 / 3
    grouped_ms = sum(harness.load_reader(m).read(recorded)
                     for m in RECORDED if m.startswith("engine_idle_ms"))
    assert 0.96 * inside_ms < grouped_ms <= inside_ms
    # the harness's own label over the same gaps, for comparison
    assert dict(recorded.trace.idle_gaps())["engine_step"] == pytest.approx(
        idle_s - by["outside"], rel=0.01)
