"""The configuration, job, traffic, reference, counts and readers of
`lfm2-train-8k` (PR 60) on the CPU at tiny widths: the real sizes run only
on the chip. The cell is found by its name; nothing here says where it
stands in the manifest or how many cells there are.
"""

import importlib.util
import json
import os
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def _load_run():
    spec = importlib.util.spec_from_file_location(
        "benchmarks_run_lfm2", os.path.join(REPO, "benchmarks", "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load_run()
from benchmarks import (  # noqa: E402
    harness, lfm2_counts, lfm2_events, lfm2_moe_reference, trace,
)
from flexflow_tpu.models import (  # noqa: E402
    lfm2_moe_reference as program_reference,
)

CELL, CONFIG = "lfm2-train-8k", "lfm2-8b-a1b"
# the catalog row's config, key for key (kept here: the catalog is not
# part of the repository)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "full_attention",
                    "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
REDUCED = {"num_hidden_layers": 6, "num_dense_layers": 1,
           "layer_types": ["conv", "full_attention", "conv", "conv", "conv",
                           "full_attention"],
           "num_experts": 8, "vocab_size": 16384}
NEW = {"train_mfu_pct.held", "sconv_ms.train", "sconv_roofline_pct.train",
       "moe_held_roofline_pct.train", "gqa_attn_roofline_pct.train"}
JOINED = {"fit_step_ms", "attn_ms.train", "device_idle_pct.train",
          "input_wait_ms.train", "moe_ms.train", "moe_dispatch_ms.train",
          "setup_import_s", "setup_search_s", "setup_weights_s",
          "setup_trace_s", "setup_programs", "setup_unnamed_s"}
# hidden 64, 4 query heads on 2 KV heads of 16, experts of 48
TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 96,
        "moe_intermediate_size": 48, "vocab_size": 97, "n_embd": 64,
        "n_head": 4, "initializer_range": 0.1,
        "embedding_initializer_range": 0.1}
FLAGS = ["--mesh", "1,1,1,1", "--no-verify-plan"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def config_file() -> dict:
    return harness.load_json("configs", CONFIG + ".json")


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    files = {
        "configs/tiny-lfm2.json": {**config_file(), **TINY},
        "traffic/tiny-train.json": {
            "kind": "train", "sequence_length": 256, "global_batch": 1,
            "steps_per_call": 3, "trace_steps_per_call": 2,
            "warmup_steps": 2},
        "workloads/tiny-lfm2-train.json": {
            "job": "train_lfm2_moe", "flags": FLAGS, "optimizer": "adam",
            "learning_rate": 1e-5,
            "attention_impl": "flash", "trace_seconds": 1},
    }
    for rel, body in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(body))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["workloads"] = [
        {"name": "tiny-lfm2-train", "config": "tiny-lfm2",
         "traffic": "tiny-train", "chips": 1, "why": "test"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (["tiny-lfm2-train"] if CELL in m["workloads"]
                              else [])
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


def test_configuration_carries_every_published_key():
    body = config_file()
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert body[key] == REDUCED[key], key
            assert body["reduced_from"][key] == value, key
        else:
            assert body[key] == value, key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the catalog, where it is at hand
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-8B-A1B")
        assert row["config"] == PUBLISHED
        assert row["source_url"] == body["source"]
    assert sorted(body["reduced"]) == sorted(REDUCED)
    assert sorted(body["reduced_from"]) == sorted(REDUCED)
    assert (body["n_embd"], body["n_head"]) == (2048, 32)
    assert "gpt2_key_aliases" in body
    assert body["experts_held"] == [0, 8] and body["experts_routed"] == 32
    assert body["tie_word_embeddings"] is True
    # the cut keeps a whole period and the layers in their published order
    assert body["layer_types"][1:] == PUBLISHED["layer_types"][2:7]
    for key in ("tie_word_embeddings", "intermediate_size", "norm_topk_eps",
                "router", "initializer_range", "embedding_initializer_range",
                "router_bias"):
        assert key in body["assumed"], key
    assert len(body["departures"]) >= 3
    assert "Four chips" in body["deployment"]
    assert "606.5 M" in body["deployment"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = run.manifest_entry(manifest, "configs", CONFIG)
    assert entry["source"] == body["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(REDUCED)
    assert len(entry["why"]) <= 200
    cell = run.manifest_entry(manifest, "workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train-8k", 1)
    assert len(cell["why"]) <= 200
    # the cell is on every metric the issue names and on no other that
    # lists its cells
    listed = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == NEW | JOINED | {"train_tok_s"}
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "train_tok_s"
            assert m["layer"] == "kernels"
    assert [m["name"] for m in run.metrics_of(
        manifest, "end_to_end", CELL)] == ["train_tok_s", "setup_s"]
    # every metric of the cell has a reader the harness finds by name
    for m in run.metrics_of(manifest, "per_layer", CELL):
        assert callable(harness.load_reader(m["name"]).read), m["name"]


def test_the_mix_and_the_cell_are_the_issues():
    mix = harness.load_json("traffic", "train-8k.json")
    assert mix["kind"] == "train" and mix["sequence_length"] == 8192
    assert mix["global_batch"] in (1, 2)
    assert mix["steps_per_call"] * mix["global_batch"] == 12
    assert (mix["trace_steps_per_call"], mix["warmup_steps"]) == (3, 2)
    cell = harness.load_json("workloads", CELL + ".json")
    assert cell["job"] == "train_lfm2_moe" and cell["optimizer"] == "adam"
    assert cell["flags"] == ["--mesh", "1,1,1,1", "--dtype", "bf16",
                             "--no-verify-plan"]
    assert cell["attention_impl"] == "flash"


def test_the_counts_against_numbers_worked_by_hand():
    c = config_file()
    d, f, v = 2048, 1792, 16384
    conv = 3 * d * d + d * d                    # 16.78 M: in_proj, out_proj
    attn = 2 * d * d + 2 * d * 512              # 10.49 M: q, o and k, v
    assert lfm2_counts.conv_mixer_params(c) == conv == 16_777_216
    assert lfm2_counts.attention_mixer_params(c) == attn == 10_485_760
    assert lfm2_counts.expert_params(c) == 3 * d * f == 11_010_048
    # the issue's 606.5 M: mixers, the dense MLP, 8 experts and a router a
    # layer, the tied embedding; norms, taps, head norms and biases on top
    matrices = (4 * conv + 2 * attn + 3 * d * 7168
                + 5 * (8 * 3 * d * f + d * 32) + v * d)
    assert round(matrices / 1e6, 1) == 606.4
    small = 13 * d + 4 * 3 * d + 2 * 2 * 64 + 5 * 32
    assert lfm2_counts.param_count(c) == matrices + small
    assert round(lfm2_counts.param_count(c) / 1e6, 1) == 606.5
    # one held assignment a token a layer (4 x 8 / 32), five layers
    flops = lfm2_counts.held_flops_per_token(c, 8192, 5.0)
    by_hand = 6 * (4 * conv + 2 * attn + 3 * d * 7168 + 5 * d * 32
                   + 5 * 3 * d * f + v * d) + 2 * 12 * d * 8192 / 2
    assert flops == by_hand and round(flops / 1e9, 2) == 1.53
    least, bound = lfm2_counts.short_conv_least_seconds(c, 8192, PEAKS)
    assert bound == "flops"
    assert least == pytest.approx(4 * 6 * 8192 * conv / 197e12)
    least, bound = lfm2_counts.held_matmul_least_seconds(c, 40960, PEAKS)
    assert bound == "flops"
    assert least == pytest.approx(18 * 40960 * d * f / 197e12)
    # a step with few held rows is bound by the matrices' bytes
    few, bound = lfm2_counts.held_matmul_least_seconds(c, 512, PEAKS)
    assert bound == "bytes"
    assert few == pytest.approx(
        18 * (512 * (d + f) + 5 * 8 * d * f) / 819e9)
    least, bound = lfm2_counts.grouped_attention_least_seconds(
        c, 8192, 1, PEAKS)
    assert bound == "flops"
    assert least == pytest.approx(2 * 6 * 8192 * 8192 * d / 197e12)


def test_the_benchmarks_reference_is_the_programs():
    mine = open(lfm2_moe_reference.__file__).read()
    assert mine == open(program_reference.__file__).read()
    for word in ("flexflow_tpu", "kernels", "import pallas"):
        assert word not in mine.split('"""', 2)[2], word
    assert 'default_matmul_precision("highest")' in mine
    job = harness.load_module("jobs", "train_lfm2_moe.py")
    assert 0 < job.LOGIT_TOL <= 0.05 and 0 < job.ATTN_FAR_TOL <= 0.1
    assert 0 < job.LOSS_TOL <= 0.01 and job.MIN_HELD_LOAD == 0.25
    assert 0 < job.TIE_MARGIN <= 0.06 and 0 < job.MAX_TAKEN_SHARE <= 0.25


def test_train_job_runs_a_window_through_fit(tiny, capsys):
    assert run.main(["--workload", "tiny-lfm2-train", "--seed", "3000000019",
                     "--seconds", "1.0", "--trace", "0"],
                    manifest_path=tiny) == 0
    out = capsys.readouterr().out
    line = result_line(out)
    assert line["correct"] is True and line["failed"] == 0, out
    assert line["attempted"] >= 3
    assert set(line["metrics"]) == {"train_tok_s", "setup_s"}
    assert "logits at [0, 128) and [128, 256)" in out
    assert "held and not computed [0, 0, 0, 0, 0]" in out
    assert "held experts' loads over the even share" in out


def test_train_job_is_not_correct_against_a_shifted_key_head(tiny,
                                                             monkeypatch):
    """The job's own control: the reference with query head i reading KV
    head i // group + 1 fails the logits and the attention alone."""
    job = harness.load_module("jobs", "train_lfm2_moe.py")
    cell = harness.load_json("workloads", "tiny-lfm2-train.json")
    import time

    ctx = harness.Context(
        cell=cell, config=harness.load_json("configs", "tiny-lfm2.json"),
        traffic=harness.load_json("traffic", "tiny-train.json"), seed=5,
        seconds=0.2, trace_dir=None, t_start=time.perf_counter(),
        compile_log=harness.CompileLog())
    result = job.run(ctx, control="kv_shift")
    c = result["counters"]
    assert result["correct"] is False
    assert c["logit_error"] > job.LOGIT_TOL
    assert c["attn_far_error"] > job.ATTN_FAR_TOL
    assert c["dropped_held"] == [0] * 5 and result["failed"] == 0
    assert len(c["assignments_held"]) == len(c["assignments_elsewhere"]) == 5
    assert all(a + b == c["tokens"] * 4 for a, b in zip(
        c["assignments_held"], c["assignments_elsewhere"]))


def test_traced_run_reads_what_it_can(tiny, capsys, monkeypatch):
    """--trace 1 with the trace steered to the recorded GPT-2 one (the CPU
    has no device plane): the job leaves the step program's instructions
    by scope, the readers join none of them to that trace's events and
    leave the device metrics out; the counters' metrics are there."""
    import jax

    with open(os.path.join(HERE, "recorded_trace.textproto")) as f:
        recorded = trace.read(
            jax.profiler.ProfileData.from_text_proto(f.read()))
    monkeypatch.setattr(trace, "read_file", lambda path: recorded)
    seen = []
    scoped = lfm2_events.scoped_instructions
    monkeypatch.setattr(
        lfm2_events, "scoped_instructions",
        lambda text: seen.append(scoped(text)) or seen[-1])
    assert run.main(["--workload", "tiny-lfm2-train", "--seed", "1",
                     "--seconds", "1", "--trace", "1"], tiny) == 0
    line = result_line(capsys.readouterr().out)
    assert line["correct"] is True
    assert {"fit_step_ms", "train_mfu_pct.held", "ffcompile_s",
            "xla_compile_s"} <= set(line["metrics"])
    assert 0 < line["metrics"]["train_mfu_pct.held"]["value"] < 100
    # (the recorded trace's instruction names are another program's: a
    # name of this step's that it happens to hold is joined, which a run's
    # own trace cannot get wrong; its kernels are the GPT-2 cell's)
    assert "moe_held_roofline_pct.train" not in line["metrics"]
    scopes = {s for _, s in seen[0]}
    assert {"sconv.proj", "sconv.conv", "sconv.out", "gqa.qkv", "gqa.repeat",
            "gqa.attend", "gqa.out", "moe.route", "moe.dispatch",
            "moe.experts", "moe.combine"} <= scopes


MS = 1_000_000
PAIRS = [["fusion.1", "sconv.proj"], ["fusion.2", "sconv.conv"],
         ["fusion.3", "sconv.out"], ["fusion.4", "gqa.qkv"],
         ["fusion.5", "moe.route"], ["fusion.6", "moe.dispatch"]]


def hand_made_run(pairs=PAIRS, config=None, counters=None):
    # (event, its milliseconds), one after the other: two steps' worth
    took = [("%fusion.1 = bf16[8192,6144] fusion(%p)", 30),
            ("%fusion.2 = f32[8192,2048] fusion(%p)", 6),
            ("%fusion.3 = bf16[8192,2048] fusion(%p)", 14),
            ("%fusion.4 = bf16[8192,2048] fusion(%p)", 1),
            ("%flash_attention_fwd_packed_grouped.1 = x", 8),
            ("%flash_attention_bwd_packed_grouped.2 = x", 24),
            ("%fusion.5 = f32[8192,32] fusion(%p)", 1),
            ("%gmm.7 = bf16[32768,1792] custom-call(%p)", 20),
            ("%tgmm.8 = bf16[8,2048,1792] custom-call(%p)", 12),
            ("%fusion.9 = f32[16384,2048] fusion(%p)", 7)]
    ops, at = [], 0
    for name, ms in took:
        ops.append((name, at * MS, (at + ms) * MS))
        at += ms
    # past the window: left out
    ops.append(("%fusion.1 = bf16[8192,6144] fusion(%p)", 270 * MS, 274 * MS))
    r = types.SimpleNamespace(
        result={"counters": {
            "steps": 2, "tokens": 2 * 8192, "lfm2_instructions": pairs,
            "assignments_held": [16384] * 5, **(counters or {})},
                "end_to_end": {"train_tok_s": 40000.0}},
        config=config_file() if config is None else config,
        traffic={"sequence_length": 8192, "global_batch": 1}, chips=1,
        peaks=PEAKS)
    r.trace = trace.Trace([trace.Chip(0, ops, [])], [], (0, 200 * MS))
    return r


def read(name, r):
    return harness.load_reader(name).read(r)


def test_lfm2_readers_on_hand_made_events():
    r, c = hand_made_run(), config_file()
    # sconv.* : 50 ms over two steps
    assert read("sconv_ms.train", r) == pytest.approx(25.0)
    least, _ = lfm2_counts.short_conv_least_seconds(c, 8192, PEAKS)
    assert read("sconv_roofline_pct.train", r) == pytest.approx(
        100 * least * 1e3 / 25.0)
    # the grouped matmuls by their own names: 32 ms over two steps, held
    # rows 5 x 16,384 / 2 a step
    least, _ = lfm2_counts.held_matmul_least_seconds(c, 40960, PEAKS)
    assert read("moe_held_roofline_pct.train", r) == pytest.approx(
        100 * least * 1e3 / 16.0)
    least, _ = lfm2_counts.grouped_attention_least_seconds(c, 8192, 1, PEAKS)
    assert read("gqa_attn_roofline_pct.train", r) == pytest.approx(
        100 * least * 1e3 / 16.0)
    # one held assignment a token a layer: 1.53 GFLOP a token
    assert read("train_mfu_pct.held", r) == pytest.approx(
        100 * 1527644160.0 * 40000.0 / 197e12)
    for name in NEW:
        assert 0 < read(name, r) < 100, name
    assert {s for _, s, _, _ in lfm2_events.events(r, "sconv.")} == {
        "sconv.proj", "sconv.conv", "sconv.out"}
    assert len(lfm2_events.events(r, "gqa.")) == 1


def test_lfm2_readers_return_none_from_a_run_that_is_not_theirs():
    gpt2 = harness.load_json("configs", "gpt2-medium.json")
    # another family's run over a trace that holds flash_attention events,
    # with and without pairs; this family's run that left no pairs
    for r in (hand_made_run(config=gpt2),
              hand_made_run(config=gpt2, pairs=[]),
              hand_made_run(pairs=[])):
        for name in NEW - {"train_mfu_pct.held"}:
            assert read(name, r) is None, name
    assert read("train_mfu_pct.held", hand_made_run(config=gpt2)) is None
    no_count = hand_made_run(counters={"assignments_held": None})
    assert read("train_mfu_pct.held", no_count) is None
    assert read("moe_held_roofline_pct.train", no_count) is None
    assert lfm2_events.events(hand_made_run(pairs=[]), "sconv.") == []


def test_scoped_instructions_keep_the_whole_scope():
    text = '''
  %fusion.3 = bf16[8,4]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/jvp(l0_attn)/sconv.proj/dot_general" source_file="x.py"}
  %fusion.4 = f32[8,4]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(step)/transpose(jvp(l2_attn))/sconv.conv/mul"}
  ROOT %fusion.5 = f32[8,4]{1,0} fusion(%p), metadata={op_name="jit(step)/jvp(l1_attn)/gqa.attend/pallas_call"}
  %fusion.6 = f32[8,4]{1,0} fusion(%p), metadata={op_name="jit(step)/jvp(l1_moe)/moe.experts/x"}
  %fusion.7 = f32[8,4]{1,0} fusion(%p), metadata={op_name="jit(step)/jvp(ln_f)/mul"}
'''
    assert lfm2_events.scoped_instructions(text) == [
        ["fusion.3", "sconv.proj"], ["fusion.4", "sconv.conv"],
        ["fusion.5", "gqa.attend"], ["fusion.6", "moe.experts"]]
    assert lfm2_events.scoped_instructions(text, r"(moe\.experts)") == [
        ["fusion.6", "moe.experts"]]
