"""The benchmark's own tests: the manifest, the generator, the arithmetic,
the trace reduction on a recorded TPU trace, the reference against the
program, and both jobs through the real fit and serve() paths -- all on the
CPU at tiny widths (TINY below; the cells' real sizes run only on the chip).

The tiny configuration, its cells, a job kind and a per-layer metric are
added as files under a temporary root plus entries of a temporary
BENCHMARK.json: what a later PR does, without editing a file that exists.
"""

import importlib.util
import json
import os
import re

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _load_run():
    spec = importlib.util.spec_from_file_location(
        "benchmarks_run", os.path.join(REPO, "benchmarks", "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load_run()
from benchmarks import harness, reference, trace, traffic  # noqa: E402

TINY = {"source": "the test file", "n_embd": 32, "n_layer": 2, "n_head": 4,
        "n_positions": 48, "n_inner": 128, "vocab_size": 97, "reduced": []}
TINY_TRAIN = {"kind": "train", "sequence_length": 16, "global_batch": 2,
              "steps_per_call": 3, "trace_steps_per_call": 2,
              "warmup_steps": 1}
TINY_CHAT = {"kind": "closed_loop", "clients": 4, "cycle": 4,
             "prompt_tokens": {"dist": "log_uniform", "min": 3, "max": 20},
             "new_tokens": {"dist": "uniform", "min": 2, "max": 6},
             "check_prompt_tokens": [3, 5, 8]}
FLAGS = ["--mesh", "1,1,1,1"]
ECHO_JOB = '''
def run(ctx):
    import jax.numpy as jnp
    total = jnp.arange(8.0).sum()
    ctx.open_window()
    with ctx.span("echo"):
        total.block_until_ready()
    ctx.close_window()
    return {"attempted": 1, "failed": 0, "correct": float(total) == 28.0,
            "end_to_end": {"echo_s": ctx.window_s}, "counters": {"n": 1}}
'''
SPAN_COUNT_METRIC = '''
def read(run):
    return float(len(run.ctx.seconds_in("fit")))
'''


@pytest.fixture
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """A root with a tiny configuration, three cells, a job kind and a
    per-layer metric as new files, and the BENCHMARK.json that names them;
    the TPU guard answers for a chip that is not there."""
    files = {
        "configs/tiny.json": TINY,
        "traffic/tiny-train.json": TINY_TRAIN,
        "traffic/tiny-chat.json": TINY_CHAT,
        "traffic/none.json": {},
        "workloads/tiny-train.json": {
            "job": "train", "flags": FLAGS, "optimizer": "adam",
            "attention_impl": "xla", "trace_seconds": 1},
        "workloads/tiny-chat.json": {
            "job": "serve", "flags": FLAGS, "optimizer": "sgd",
            "attention_impl": "xla", "train_batch": 1, "trace_seconds": 1,
            # no prefix sharing: with 97 tokens to draw from, prompts
            # begin alike by chance, and a shared first token shifts the
            # prefill shapes from run to run
            "serve": {"slots": 4, "max_seq_len": 32, "prefill_chunk": 8,
                      "kv_layout": "paged", "kv_block_size": 4,
                      "prefix_sharing": False}},
        "workloads/tiny-echo.json": {"job": "echo", "trace_seconds": 1},
    }
    for rel, body in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(body))
    (tmp_path / "jobs").mkdir()
    (tmp_path / "jobs" / "echo.py").write_text(ECHO_JOB)
    (tmp_path / "layer_metrics").mkdir()
    (tmp_path / "layer_metrics" / "fit_calls.py").write_text(
        SPAN_COUNT_METRIC)
    cells = [("tiny-train", "tiny-train"), ("tiny-chat", "tiny-chat"),
             ("tiny-echo", "none")]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["workloads"] = [{"name": c, "config": "tiny", "traffic": t,
                         "chips": 1, "why": "test"} for c, t in cells]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            job = ("train" if "train" in m["name"] or m["name"] == "fit_step_ms"
                   else "chat")
            m["workloads"] = ["tiny-" + job]
    doc["end_to_end"].append({"name": "echo_s", "unit": "s",
                              "workloads": ["tiny-echo"]})
    doc["per_layer"].append({"name": "fit_calls", "unit": "calls",
                             "workloads": ["tiny-train"]})
    manifest_path = tmp_path / "BENCHMARK.json"
    manifest_path.write_text(json.dumps(doc))
    # main() defaults the variable for the process it thinks it owns
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(harness, "ROOTS", [harness.HERE, str(tmp_path)])
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))
    monkeypatch.setattr(run, "find_device", lambda chips: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": chips})
    return str(manifest_path)


def result_line(capsys) -> dict:
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_manifest_meets_the_contract(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"] for c in manifest["configs"]}
    assert configs == {w["config"] for w in cells.values()}
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == len(
        cells)
    four = sum(w["chips"] == 4 for w in cells.values())
    assert four <= max(1, len(cells) // 4)
    reports = {}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        reports[m["name"]] = set(m.get("workloads", cells))
        assert reports[m["name"]] <= set(cells)
    for m in manifest["end_to_end"]:
        assert 0 < m["bound"] <= 0.1 and m["source"] == "host_clock"
    assert reports["setup_s"] == set(cells)
    for m in manifest["per_layer"]:
        assert "bound" not in m
        assert reports[m["name"]] <= reports[m["moves"]], m
        assert callable(harness.load_reader(m["name"]).read)
    for name, w in cells.items():
        assert NAME.match(name) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200
        cell = harness.load_json("workloads", name + ".json")
        harness.find_file("jobs", cell["job"] + ".py")
        harness.load_json("traffic", w["traffic"] + ".json")
        assert any(name in reports[m["name"]] and m["name"] != "setup_s"
                   for m in manifest["end_to_end"])
        assert any(name in reports[m["name"]] for m in manifest["per_layer"])
    for c in manifest["configs"]:
        assert c["file"].startswith(tuple(manifest["paths"]))
        body = harness.load_json("configs", c["name"] + ".json")
        assert body["source"] == c["source"] and body["reduced"] == c[
            "reduced"]
        assert body["n_embd"] % body["n_head"] == 0
    assert run.metrics_of(manifest, "end_to_end", "c13b-serve-chat")[0][
        "name"] == "serve_tok_s"


def test_traffic_is_the_seeds_and_every_seed_does_the_same_work():
    def take(seed, n):
        stream = traffic.requests(TINY_CHAT, 97, seed)
        return [next(stream) for _ in range(n)]

    def sizes(reqs):
        return [(len(p), m) for p, m in reqs]

    prompts, replies = traffic.request_sizes(TINY_CHAT)
    assert len(prompts) == len(replies) == 4
    assert all(3 <= p <= 20 for p in prompts)
    assert all(2 <= m <= 6 for m in replies)
    a, b, c = take(5, 12), take(5, 12), take(2**31 + 7, 12)
    assert a == b and a != c
    # another seed: the same sizes each cycle, paired and ordered anew
    assert sizes(a) != sizes(c)
    assert len({tuple(sizes(a[lo:lo + 4])) for lo in (0, 4, 8)}) > 1
    for reqs in (a, c):  # each cycle holds every size once
        for lo in range(0, 12, 4):
            assert sorted(len(p) for p, _ in reqs[lo:lo + 4]) == prompts
            assert sorted(m for _, m in reqs[lo:lo + 4]) == replies
        assert all(0 <= t < 97 for p, _ in reqs for t in p)
    chat = harness.load_json("traffic", "serve-chat.json")
    prompts, replies = traffic.request_sizes(chat)
    assert len(prompts) == chat["cycle"] == chat["clients"]
    assert max(prompts) + max(replies) <= 640
    assert max(prompts) > 3 * 128   # a prompt of four chunks every cycle
    x, y = traffic.train_batches(TINY_TRAIN, 97, 3, steps=3)
    x2, y2 = traffic.train_batches(TINY_TRAIN, 97, 3, steps=3)
    assert x["tokens"].shape == (6, 16) and y.shape == (6, 16, 1)
    assert np.array_equal(x["tokens"], x2["tokens"])
    assert np.array_equal(x["tokens"][:, 1:], y[:, :-1, 0])  # next token
    assert len({r.tobytes() for r in x["tokens"]}) == 6      # distinct


def test_percentiles_and_request_latencies_on_hand_made_stamps():
    serve = harness.load_module("jobs", "serve.py")
    rs = np.random.RandomState(0).rand(41)
    for q in (0, 50, 95, 100):
        assert harness.percentile(rs, q) == pytest.approx(
            np.percentile(rs, q))
    assert harness.percentile([1, 2, 3, 4], 95) == pytest.approx(3.85)
    with pytest.raises(ValueError):
        harness.percentile([], 50)

    class Req:
        def __init__(self, submit, first, finish, n):
            self.submit_t, self.first_token_t, self.finish_t = (
                submit, first, finish)
            self.generated, self.finished = list(range(n)), True

    reqs = [Req(10.0, 10.5, 12.5, 5), Req(11.0, 11.25, 11.25, 1)]
    ttft, tpot = serve.request_latencies(reqs)
    assert ttft == [0.5, 0.25] and tpot == [0.5]  # one token: no gap
    assert serve.came_back_right(reqs[0], 5, 97)
    assert not serve.came_back_right(reqs[0], 6, 97)
    assert not serve.came_back_right(reqs[0], 5, 4)
    stats = serve.latency_statistics([0.1, 0.2, 0.3, 0.4], [0.05])
    assert stats["ttft_ms.mean"] == pytest.approx(250.0)
    assert stats["ttft_ms.p95"] == pytest.approx(385.0)
    assert stats["tpot_ms.p50"] == pytest.approx(50.0)
    assert serve.latency_statistics([], [])["ttft_ms.mean"] is None


def test_a_stream_is_held_to_the_references_logits():
    serve = harness.load_module("jobs", "serve.py")
    rows = np.zeros((3, 50), np.float32)
    rows[0, 7], rows[1, 9], rows[2, 11] = 10.0, 10.0, 10.0
    margin = 2 * reference.LOGIT_TOL * 10.0
    rows[1, 4] = 10.0 - 0.5 * margin      # a tie within the tolerance
    rows[2, 5] = 10.0 - 2.0 * margin      # too far from the top
    assert reference.stream_agrees(rows, [7, 9, 11])
    assert reference.stream_agrees(rows, [7, 4, 11])
    assert not reference.stream_agrees(rows, [7, 9, 5])
    assert not reference.stream_agrees(rows, [7, 9])     # a token short
    assert not reference.stream_agrees(rows[:0], [])

    class Req:
        def __init__(self, i, n):
            self.request_id, self.prompt = i, [0] * n

    reqs = [Req(i, n) for i, n in enumerate([5, 40, 9, 300, 120, 470, 33])]
    picked = [len(r.prompt) for r in serve.spread_by_prompt(reqs, 4)]
    assert picked == [5, 33, 120, 470]
    assert serve.spread_by_prompt(reqs[:2], 4) == reqs[:2]


def test_flop_and_roofline_arithmetic():
    cfg = {"n_embd": 4, "n_layer": 2, "n_inner": 16, "vocab_size": 10}
    # 2 layers x (4 x 16 + 2 x 64) matmul parameters + a 40-parameter head
    assert harness.flops_per_token(cfg, 8) == 6 * (2 * 192 + 40) + 2 * 12 * 4 * 8 / 2
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e9}
    least, bound = harness.attention_least_seconds(cfg, 8, 3, peaks)
    assert bound == "flops" and least == pytest.approx(
        2 * 6 * 3 * 8 * 8 * 4 / 1e3)
    _, bound = harness.attention_least_seconds(
        cfg, 8, 3, {"bf16_flops_per_s": 1e15, "hbm_bytes_per_s": 1.0})
    assert bound == "bytes"
    v5e = harness.load_json("peaks.json")["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    gpt2m = harness.load_json("configs", "gpt2-medium.json")
    # 8,192 tokens a step of gpt2-medium at 1024 context: 18.6 TFLOP
    assert 8192 * harness.flops_per_token(gpt2m, 1024) == pytest.approx(
        18.6e12, rel=0.01)


def test_trace_arithmetic_by_hand():
    assert trace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert trace.total([(1, 4), (5, 8)]) == 6
    assert trace.clip([(1, 4), (5, 8)], 2, 6) == [(2, 4), (5, 6)]
    assert trace.gaps([(1, 4), (5, 8)], 0, 10) == [(0, 1), (4, 5), (8, 10)]
    assert trace.op_name("%flash_attention_fwd.2 = (bf16[4]) custom-call("
                         ) == "flash_attention_fwd.2"
    assert trace.op_family("%all-gather-start.3.1 = x") == "all-gather-start"
    assert trace.COLLECTIVE.match("all-gather-start.3")
    assert not trace.COLLECTIVE.match("fusion.3")
    chip = trace.Chip(0, [("%a.1 = x", 100, 200), ("%b = y", 150, 300),
                          ("%a.2 = x", 700, 800)], [])
    t = trace.Trace([chip], [("bench/window", 0, 1000),
                             ("bench/fit", 0, 400),
                             ("bench/submit", 350, 380)], (0, 1000))
    assert t.busy(0) == [(100, 300), (700, 800)]
    assert t.idle_pct(0) == pytest.approx(70.0)
    assert t.device_ops() == [["a", 2e-7], ["b", 1.5e-7]]
    assert t.seconds_of(lambda n: n.startswith("a")) == pytest.approx(2e-7)
    # 0-100 and 300-350 and 380-400 under fit, 350-380 under submit inside
    # it, 400-700 and 800-1000 under nothing
    assert t.idle_gaps() == [["outside", pytest.approx(5e-7)],
                             ["fit", pytest.approx(1.7e-7)],
                             ["submit", pytest.approx(3e-8)]]


def test_trace_reduction_on_a_recorded_tpu_trace():
    """recorded_trace.textproto: chip 0 and the harness spans of a v5e trace
    of this repo's train step (PR 24), cut to the end of one fit call, the
    50 ms the host then slept, and the start of the next call."""
    with open(os.path.join(HERE, "recorded_trace.textproto")) as f:
        t = trace.read(jax.profiler.ProfileData.from_text_proto(f.read()))
    assert len(t.chips) == 1 and len(t.chips[0].ops) == 735
    assert [s[0] for s in t.spans] == ["bench/fit", "bench/fit"]
    assert t.window_s == pytest.approx(0.0601, abs=1e-4)  # no window span
    assert t.busy_s(0) == pytest.approx(407.6e-6, rel=1e-3)
    assert 99.0 < t.idle_pct(0) < 99.5
    families = dict(t.device_ops())
    assert families["flash_attention_fwd_packed"] == pytest.approx(
        18.78e-6, rel=1e-2)
    assert t.seconds_of(lambda n: n.startswith("flash_attention")
                        ) == pytest.approx(49.2e-6, rel=1e-2)
    gaps = dict(t.idle_gaps())
    assert gaps["outside"] == pytest.approx(0.0506, rel=1e-2)  # the sleep
    assert gaps["fit"] == pytest.approx(0.0091, rel=2e-2)
    with pytest.raises(ValueError, match="no device operation"):
        trace.read(jax.profiler.ProfileData.from_text_proto(
            'planes { id: 1 name: "/host:CPU" }'))


def test_reference_agrees_with_the_programs_logits():
    """float32 on the CPU: the reference and the training graph compute the
    same block, so they agree to rounding, far inside the chip tolerance
    (which has to admit bf16); a wrong block would not."""
    cfg = harness.lm_config(TINY, 16, "xla")
    ff = harness.build_lm(cfg, FLAGS + ["--seed", "11"], 2, "sgd")
    x, _ = traffic.train_batches(TINY_TRAIN, 97, 4, steps=1)
    ff.start_batch(x, np.zeros((2, 16, 1), np.int32))
    program = np.asarray(ff.forward(), np.float32)
    ref = reference.forward_logits(harness.param_getter(ff), x["tokens"],
                                   num_layers=2, num_heads=4)
    assert reference.logit_error(program, ref) < 1e-4 < reference.LOGIT_TOL
    shuffled = reference.forward_logits(
        harness.param_getter(ff), x["tokens"], num_layers=2, num_heads=2)
    assert reference.logit_error(program, shuffled) > reference.LOGIT_TOL
    assert reference.logit_error(program * np.nan, ref) == float("inf")
    labels = x["tokens"]
    assert reference.loss(ref, labels) == pytest.approx(
        float(np.mean([-np.log(np.exp(r[l]) / np.exp(r).sum())
                       for r, l in zip(ref.reshape(-1, 97),
                                       labels.reshape(-1))])), rel=1e-5)


def test_train_job_runs_a_window_through_fit(tiny, capsys):
    assert run.main(["--workload", "tiny-train", "--seed", str(2**31 + 5),
                     "--seconds", "1", "--trace", "0"], tiny) == 0
    line = result_line(capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3 and line["attempted"] % 3 == 0
    assert set(line["metrics"]) == {"train_tok_s", "setup_s"}
    assert line["metrics"]["train_tok_s"]["value"] > 0
    assert line["metrics"]["train_tok_s"]["unit"] == "tokens/s"
    assert line["device"]["platform"] == "tpu" and "breakdown" not in line


def test_serve_job_runs_a_window_through_serve(tiny, capsys):
    assert run.main(["--workload", "tiny-chat", "--seed", "9",
                     "--seconds", "1", "--trace", "0"], tiny) == 0
    out = capsys.readouterr().out
    line = json.loads(out.strip().splitlines()[-1])
    assert "0 of 4 off it" in out   # streams of the loop, by the reference
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 4
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        named = {m["name"] for m in run.metrics_of(
            json.load(f), "end_to_end", "c13b-serve-chat")}
    assert set(line["metrics"]) == named > {"serve_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_traced_run_reports_the_per_layer_metrics(tiny, capsys, monkeypatch):
    """--trace 1 with the trace file steered to the recorded one (a CPU
    run's trace holds no TPU plane, which the reader refuses): the cell's
    per-layer metrics, a metric added as a file, busy_s, window_s and the
    breakdown."""
    with open(os.path.join(HERE, "recorded_trace.textproto")) as f:
        recorded = trace.read(
            jax.profiler.ProfileData.from_text_proto(f.read()))
    monkeypatch.setattr(trace, "read_file", lambda path: recorded)
    assert run.main(["--workload", "tiny-train", "--seed", "1",
                     "--seconds", "30", "--trace", "1"], tiny) == 0
    line = result_line(capsys)
    assert line["correct"] is True
    assert set(line["metrics"]) == {
        "ffcompile_s", "xla_compile_s", "fit_step_ms", "train_mfu_pct",
        "attn_ms.train", "attn_roofline_pct.train", "device_idle_pct.train",
        "collective_ms.train", "fit_calls"}
    assert line["metrics"]["collective_ms.train"]["value"] == 0.0
    assert line["metrics"]["fit_calls"] == {
        "value": line["attempted"] / 2, "unit": "calls"}  # 2 steps a call
    assert 0 < line["device"]["busy_s"] < line["device"]["window_s"] < 1
    assert line["breakdown"]["device_ops"][0][0] == "fusion"
    assert len(line["breakdown"]["idle_gaps"]) <= 10


def test_a_job_kind_is_a_file_and_serve_readers_read_its_counters(
        tiny, capsys):
    assert run.main(["--workload", "tiny-echo", "--seed", "0",
                     "--seconds", "1", "--trace", "0"], tiny) == 0
    line = result_line(capsys)
    assert line["correct"] is True and set(line["metrics"]) == {
        "echo_s", "setup_s"}

    class Run:
        result = {"counters": {"step_s": [0.01, 0.03, 0.02],
                               "prefill_step_s": [0.03]}}
        ctx = type("Ctx", (), {"window_s": 0.1})()

    read = lambda name: harness.load_module(  # noqa: E731
        "layer_metrics", name + ".py").read(Run)
    assert read("engine_iter_ms") == pytest.approx(20.0)
    assert read("prefill_share_pct") == pytest.approx(30.0)
    Run.result = {"counters": {}}
    assert read("engine_iter_ms") is None  # nothing to read: left out


def test_without_a_tpu_the_run_exits_nonzero_and_prints_no_result(
        capsys, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with pytest.raises(SystemExit) as stop:
        run.main(["--workload", "gpt2m-train-1k", "--seed", "0",
                  "--seconds", "1", "--trace", "0"])
    assert stop.value.code not in (0, None)
    assert "{" not in capsys.readouterr().out
    with pytest.raises(SystemExit):
        run.main(["--workload", "no-such-cell", "--seconds", "1"])
