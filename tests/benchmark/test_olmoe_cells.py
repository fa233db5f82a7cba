"""The two jobs of PR 27 on the CPU at tiny widths, as test_benchmark.py
does it for the first three cells: `olmoe-train-4k`'s job
(jobs/train_moe_lm.py), its reference and its four readers, and the open
serving loop (jobs/serve_open.py) with its two readers, whose cell
`c13b-serve-open` is not in BENCHMARK.json yet (its files are; PERF.md
section 7). The real sizes run only on the chip.
"""

import importlib.util
import json
import os
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def _load_run():
    spec = importlib.util.spec_from_file_location(
        "benchmarks_run_olmoe", os.path.join(REPO, "benchmarks", "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load_run()
from benchmarks import harness, moe_events, olmoe_reference, trace  # noqa: E402
from flexflow_tpu.models import olmoe_reference as program_reference  # noqa: E402

PUBLISHED = {  # the catalog row's config, key for key
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}
TINY_OLMOE = {**PUBLISHED, "source": "the test file", "hidden_size": 32,
              "intermediate_size": 16, "max_position_embeddings": 48,
              "num_attention_heads": 4, "num_key_value_heads": 4,
              "num_experts": 4, "num_experts_per_tok": 2,
              "num_hidden_layers": 2, "vocab_size": 97,
              "router_aux_loss_coef": 0.01, "reduced": []}
TINY_GPT2 = {"source": "the test file", "n_embd": 32, "n_layer": 2,
             "n_head": 4, "n_positions": 48, "n_inner": 128,
             "vocab_size": 97, "reduced": []}
TINY_TRAIN = {"kind": "train", "sequence_length": 16, "global_batch": 2,
              "steps_per_call": 3, "trace_steps_per_call": 2,
              "warmup_steps": 1}
TINY_OPEN = {"kind": "open_loop", "rate": 40.0, "schedule_seed": 7,
             "cycle": 4,
             "prompt_tokens": {"dist": "log_uniform", "min": 3, "max": 20},
             "new_tokens": {"dist": "uniform", "min": 2, "max": 6},
             "check_prompt_tokens": [3, 5, 8]}
FLAGS = ["--mesh", "1,1,1,1"]


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """A root with the two tiny configurations and a cell for each of the
    two new jobs, and the BENCHMARK.json that names them."""
    files = {
        "configs/tiny-olmoe.json": TINY_OLMOE,
        "configs/tiny.json": TINY_GPT2,
        "traffic/tiny-train.json": TINY_TRAIN,
        "traffic/tiny-open.json": TINY_OPEN,
        "workloads/tiny-moe-train.json": {
            "job": "train_moe_lm", "flags": FLAGS, "optimizer": "adam",
            "attention_impl": "xla", "trace_seconds": 1},
        "workloads/tiny-open.json": {
            "job": "serve_open", "flags": FLAGS, "optimizer": "sgd",
            "attention_impl": "xla", "train_batch": 1, "trace_seconds": 1,
            "trace_lead_seconds": 0.5,
            "serve": {"slots": 4, "max_seq_len": 32, "prefill_chunk": 8,
                      "kv_layout": "paged", "kv_block_size": 4,
                      "prefix_sharing": False}},
    }
    for rel, body in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(body))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["workloads"] = [
        {"name": "tiny-moe-train", "config": "tiny-olmoe",
         "traffic": "tiny-train", "chips": 1, "why": "test"},
        {"name": "tiny-open", "config": "tiny", "traffic": "tiny-open",
         "chips": 1, "why": "test"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [
                "tiny-moe-train" if "olmoe-train-4k" in m["workloads"]
                else "tiny-open"]
    manifest_path = tmp_path / "BENCHMARK.json"
    manifest_path.write_text(json.dumps(doc))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(harness, "ROOTS", [harness.HERE, str(tmp_path)])
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))
    monkeypatch.setattr(run, "find_device", lambda chips: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": chips})
    return str(manifest_path)


def result_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def test_configuration_carries_every_published_width():
    body = harness.load_json("configs", "olmoe-1b-7b.json")
    for key, value in PUBLISHED.items():
        if key != "num_hidden_layers":
            assert body[key] == value, key
    assert body["reduced"] == ["num_hidden_layers"]
    assert body["num_hidden_layers"] == 1
    assert body["reduced_from"] == {"num_hidden_layers": 16}
    assert "router_aux_loss_coef" in body["assumed"]
    assert len(body["departures"]) >= 2
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = run.manifest_entry(manifest, "configs", "olmoe-1b-7b")
    assert entry["source"] == body["source"]
    assert entry["reduced"] == body["reduced"]
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells["olmoe-train-4k"]["chips"] == 1
    assert sum(w["chips"] == 4 for w in cells.values()) == 1
    # its files are here and tested below; its entry waits for a
    # `benchmark` PR (PERF.md section 7: the admission test's spreads)
    assert "c13b-serve-open" not in cells


def test_open_loop_mix_is_the_chat_mix_on_its_own_clock():
    chat = harness.load_json("traffic", "serve-chat.json")
    mix = harness.load_json("traffic", "serve-chat-open.json")
    for key in ("prompt_tokens", "new_tokens", "cycle",
                "check_prompt_tokens"):
        assert mix[key] == chat[key], key
    assert mix["kind"] == "open_loop" and mix["rate"] > 0
    closed = harness.load_json("workloads", "c13b-serve-chat.json")
    cell = harness.load_json("workloads", "c13b-serve-open.json")
    assert cell["serve"] == closed["serve"] and cell["flags"] == closed[
        "flags"]


def test_flop_and_roofline_counts_of_the_expert_layer():
    counts = harness.load_module("olmoe_counts.py")
    cfg = harness.load_json("configs", "olmoe-1b-7b.json")
    # one layer at 4,096: 16.8 M attention + 0.13 M router + 50.3 M active
    # expert + 103.0 M head parameters, six FLOPs each, and 50.3 MFLOP of
    # causal attention
    assert counts.active_flops_per_token(cfg, 4096) == pytest.approx(
        1071.9e6, rel=1e-4)
    full = dict(cfg, num_hidden_layers=16)
    head = 6.0 * 2048 * 50304
    assert head / counts.active_flops_per_token(cfg, 4096) == pytest.approx(
        0.58, abs=0.01)
    assert head / counts.active_flops_per_token(full, 4096) == pytest.approx(
        0.08, abs=0.01)
    peaks = harness.load_json("peaks.json")["TPU v5 lite"]
    least, bound = counts.grouped_matmul_least_seconds(cfg, 16384, peaks)
    assert bound == "flops"
    assert least == pytest.approx(18 * 16384 * 8 * 2048 * 1024 / 197e12)
    _, bound = counts.grouped_matmul_least_seconds(
        cfg, 16384, {"bf16_flops_per_s": 1e18, "hbm_bytes_per_s": 1.0})
    assert bound == "bytes"


def test_arrival_schedule_is_the_mixs_not_the_runs():
    job = harness.load_module("jobs", "serve_open.py")
    a = job.arrival_offsets(3.6, 11, 45.0)
    b = job.arrival_offsets(3.6, 11, 45.0)
    c = job.arrival_offsets(3.6, 12, 45.0)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert np.all(np.diff(a) > 0) and a[-1] > 45.0
    assert np.mean(np.diff(a)) == pytest.approx(1 / 3.6, rel=0.15)


def test_the_benchmarks_reference_is_the_programs():
    """Two files on purpose (a later PR cannot move the yardstick by
    editing the program's copy); they have to say the same."""
    rng = np.random.default_rng(0)
    d, n, f, v = 32, 4, 16, 97
    norm = lambda: rng.uniform(0.5, 1.5, d).astype(np.float32)  # noqa: E731
    w = lambda *s: (rng.normal(size=s) * 0.2).astype(np.float32)  # noqa: E731
    params = {"wte": {"kernel": w(v, d)}, "ln_f": {"scale": norm()},
              "lm_head": {"kernel": w(d, v)},
              "l0_ln1": {"scale": norm()}, "l0_ln2": {"scale": norm()},
              "l0_attn": {"wq": w(d, d), "wk": w(d, d), "wv": w(d, d),
                          "wo": w(d, d), "q_norm": norm(), "k_norm": norm()},
              "l0_moe": {"router": w(d, n), "gate": w(n, d, f),
                         "up": w(n, d, f), "down": w(n, f, d)}}
    tokens = rng.integers(0, v, (2, 12))
    pos = np.tile(np.arange(12), (2, 1))
    kw = dict(num_layers=1, num_heads=4, num_experts_per_tok=2)
    mine, _ = olmoe_reference.forward(params, tokens, pos, **kw)
    theirs, _ = program_reference.forward(params, tokens, pos, **kw)
    assert np.array_equal(np.asarray(mine), np.asarray(theirs))
    labels = rng.integers(0, v, (2, 12))
    assert float(olmoe_reference.loss(
        params, tokens, pos, labels, router_aux_loss_coef=0.01, **kw)
    ) == float(program_reference.loss(
        params, tokens, pos, labels, router_aux_loss_coef=0.01, **kw))
    assert olmoe_reference.LOGIT_TOL == 0.03
    assert 0 < olmoe_reference.TIE_MARGIN < 0.1
    assert olmoe_reference.MAX_TAKEN_SHARE == 0.25


def test_moe_train_job_runs_a_window_through_fit(tiny, capsys):
    assert run.main(["--workload", "tiny-moe-train", "--seed",
                     str(2**31 + 5), "--seconds", "1", "--trace", "0"],
                    tiny) == 0
    out = capsys.readouterr().out
    line = result_line(out)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3 and line["attempted"] % 3 == 0
    assert set(line["metrics"]) == {"train_tok_s", "setup_s"}
    assert line["metrics"]["train_tok_s"]["value"] > 0
    assert "0 assignments dropped" in out
    # float32 against float32: no expert is swapped, the logits agree
    assert "routed 0 positions unlike the reference" in out


def test_moe_train_job_is_not_correct_when_the_logits_are_off(
        tiny, capsys, monkeypatch):
    monkeypatch.setattr(olmoe_reference, "LOGIT_TOL", 1e-12)
    assert run.main(["--workload", "tiny-moe-train", "--seed", "3",
                     "--seconds", "0.2", "--trace", "0"], tiny) == 0
    assert result_line(capsys.readouterr().out)["correct"] is False


def test_traced_moe_run_reads_what_it_can(tiny, capsys, monkeypatch):
    """--trace 1 with the trace steered to the recorded GPT-2 one: the
    job compiles the step's text for the scoped instructions and the
    readers match them to that trace's events by name (`fusion.3` is a
    name both programs have, so the two times are of no meaning here; no
    `ragged-dot` is among them, so the roofline share is left out)."""
    import jax

    with open(os.path.join(HERE, "recorded_trace.textproto")) as f:
        recorded = trace.read(
            jax.profiler.ProfileData.from_text_proto(f.read()))
    monkeypatch.setattr(trace, "read_file", lambda path: recorded)
    seen = {}
    scoped = moe_events.scoped_instructions
    monkeypatch.setattr(
        moe_events, "scoped_instructions",
        lambda text: seen.setdefault("pairs", scoped(text)))
    assert run.main(["--workload", "tiny-moe-train", "--seed", "1",
                     "--seconds", "30", "--trace", "1"], tiny) == 0
    line = result_line(capsys.readouterr().out)
    assert line["correct"] is True
    assert set(line["metrics"]) - {"moe_ms.train", "moe_dispatch_ms.train"
                                   } == {
        "ffcompile_s", "xla_compile_s", "fit_step_ms", "attn_ms.train",
        "device_idle_pct.train", "train_mfu_pct.active"}
    assert {"route", "dispatch", "experts", "combine"} == {
        s for _, s in seen["pairs"]}


def test_open_loop_job_runs_a_window_through_serve(tiny, capsys):
    assert run.main(["--workload", "tiny-open", "--seed", "9",
                     "--seconds", "1.5", "--trace", "0"], tiny) == 0
    out = capsys.readouterr().out
    line = result_line(out)
    assert "off it" in out and "0 of" in out
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 4
    assert set(line["metrics"]) == {"serve_tok_s", "tpot_ms.p90", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())


def _run(**counters):
    return types.SimpleNamespace(
        result={"counters": counters, "end_to_end": {}},
        traffic={"global_batch": 4, "sequence_length": 4096}, chips=1,
        config=harness.load_json("configs", "olmoe-1b-7b.json"),
        peaks=harness.load_json("peaks.json")["TPU v5 lite"])


def reader(name):
    return harness.load_reader(name).read


HLO = '''
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f.1, metadata={op_name="jit(step)/jvp(l0_moe)/moe.route/dot_general" stack_frame_id=3}
  %sort.2 = s32[8]{0} sort(%x), dimensions={0}, metadata={op_name="jit(step)/jvp(l0_moe)/moe.dispatch/jit(argsort)/sort"}
  %ragged-dot-none.3 = bf16[8,4]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %multiply_fusion = bf16[8,4]{1,0} fusion(%ragged-dot-none.3), kind=kLoop, calls=%f.2, metadata={op_name="jit(step)/jvp(l0_moe)/moe.experts/mul"}
  ROOT %gather.4 = bf16[8,4]{1,0} gather(%y), metadata={op_name="jit(step)/transpose(jvp(l0_moe))/moe.combine/gather"}
  %divide_subtract_fusion = f32[4]{0} fusion(%ragged-dot-none.9), kind=kLoop, calls=%f.3, metadata={op_name="jit(step)/weight_update/sub"}
  %flash_attention_fwd = bf16[4]{0} custom-call(%q), metadata={op_name="jit(step)/l0_attn/flash_attention_fwd/pallas_call"}
'''


def test_moe_readers_on_hand_made_events():
    pairs = moe_events.scoped_instructions(HLO)
    for name in ("gmm.4", "tgmm", "ragged-dot-none.12", "grouped_matmul_dw"):
        assert moe_events.is_grouped_matmul(name), name
    for name in ("fusion.3", "gmmx", "copy.gmm"):
        assert not moe_events.is_grouped_matmul(name), name
    assert pairs == [["fusion.1", "route"], ["sort.2", "dispatch"],
                     ["multiply_fusion", "experts"], ["gather.4", "combine"]]
    ms = 1_000_000
    ops = [("%fusion.1 = f32[8] fusion(f32[8] %p)", 0, 1 * ms),
           ("%sort.2 = s32[8] sort(s32[8] %x)", 1 * ms, 3 * ms),
           # XLA's own kernels for jax.lax.ragged_dot carry no scope
           ("%ragged-dot-none.3 = bf16[8,4] custom-call(%a)", 3 * ms,
            13 * ms),
           ("%multiply_fusion = bf16[8,4] fusion(%ragged-dot-none.3)",
            13 * ms, 14 * ms),
           ("%tgmm.9 = f32[4,2,2] custom-call(%a)", 14 * ms, 34 * ms),
           ("%gather.4 = bf16[8,4] gather(%y)", 34 * ms, 36 * ms),
           # reads a grouped matmul's result, belongs to the update
           ("%divide_subtract_fusion = f32[4] fusion(%tgmm.9)",
            36 * ms, 40 * ms),
           ("%flash_attention_fwd = bf16[4] custom-call(%q)", 40 * ms,
            44 * ms)]
    r = _run(steps=2, moe_instructions=pairs)
    r.trace = trace.Trace([trace.Chip(0, ops, [])], [], (0, 50 * ms))
    assert [e[:2] for e in moe_events.events(r)] == [
        ("fusion.1", "route"), ("sort.2", "dispatch"),
        ("ragged-dot-none.3", "experts"), ("multiply_fusion", "experts"),
        ("tgmm.9", "experts"), ("gather.4", "combine")]
    assert reader("moe_ms.train")(r) == pytest.approx(18.0)
    assert reader("moe_dispatch_ms.train")(r) == pytest.approx(3.0)
    least_ms = 18 * 16384 * 8 * 2048 * 1024 / 197e12 * 1e3
    assert reader("moe_roofline_pct.train")(r) == pytest.approx(
        100 * least_ms / 15.0)
    nothing = _run(steps=2)      # a job that left no scoped instructions
    nothing.trace = r.trace
    for name in ("moe_ms.train", "moe_dispatch_ms.train",
                 "moe_roofline_pct.train"):
        assert reader(name)(nothing) is None


def test_active_mfu_and_the_open_loops_readers_read_the_jobs_numbers():
    r = _run()
    r.result["end_to_end"]["train_tok_s"] = 80000.0
    assert reader("train_mfu_pct.active")(r) == pytest.approx(
        100 * 1071.9e6 * 80000 / 197e12, rel=1e-4)
    r.config = TINY_GPT2     # a GPT-2 cell: nothing to count experts on
    assert reader("train_mfu_pct.active")(r) is None
    r = _run(**{"ttft_from_due_ms.p90": 412.5, "queue_wait_ms.p90": 31.0})
    assert reader("ttft_ms.p90")(r) == 412.5
    assert reader("queue_wait_ms.p90")(r) == 31.0
    closed = _run(step_s=[0.01])   # the closed loop stamps no due instants
    assert reader("ttft_ms.p90")(closed) is None
    assert reader("queue_wait_ms.p90")(closed) is None
