"""The configuration, job, traffic, reference and readers of
`jamba2-serve-shortchat` (PR 55) on the CPU at tiny widths, as
test_solar2_cell.py does it for PR 33's: the real sizes run only on the
chip.
"""

import importlib.util
import json
import math
import os
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def _load_run():
    spec = importlib.util.spec_from_file_location(
        "benchmarks_run_jamba2", os.path.join(REPO, "benchmarks", "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load_run()
from benchmarks import (  # noqa: E402
    harness, jamba2_events, jamba2_reference, trace, traffic,
)
from flexflow_tpu.models import (  # noqa: E402
    jamba2_reference as program_reference,
)

# the catalog row's config, key for key (kept here: the catalog is not
# part of the repository)
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536}
CELL = "jamba2-serve-shortchat"
# hidden 64, inner 128, state 16, dt rank 8, 4 query heads over 1 KV head;
# two periods of mamba, mamba, attention, mamba
TINY = {
    **PUBLISHED, "source": "the test file", "hidden_size": 64,
    "intermediate_size": 96, "num_attention_heads": 4, "n_embd": 64,
    "n_head": 4, "num_hidden_layers": 8, "attn_layer_period": 4,
    "attn_layer_offset": 2, "mamba_dt_rank": 8, "vocab_size": 97,
    "initializer_range": 0.1, "reduced": []}
TINY_CHAT = {
    "kind": "closed_loop", "clients": 3, "cycle": 3,
    "prompt_tokens": {"dist": "log_uniform", "min": 5, "max": 20},
    "new_tokens": {"dist": "uniform", "min": 3, "max": 9},
    "check_prompt_tokens": [13]}
FLAGS = ["--mesh", "1,1,1,1", "--no-verify-plan"]
NEW = {"ssm_state_ms.serve", "ssm_state_roofline_pct.serve",
       "ssm_mix_ms.serve", "ssm_scan_ms.serve", "mqa_decode_ms.serve"}


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    files = {
        "configs/tiny-jamba2.json": TINY,
        "traffic/tiny-shortchat.json": TINY_CHAT,
        "workloads/tiny-shortchat.json": {
            "job": "serve_shortchat", "flags": FLAGS, "optimizer": "sgd",
            "attention_impl": "xla", "train_batch": 1,
            "train_sequence_length": 16, "trace_seconds": 1,
            "serve": {"slots": 3, "max_seq_len": 32, "prefill_chunk": 8,
                      "kv_layout": "paged", "kv_block_size": 4,
                      "kv_num_blocks": 40, "prefix_cache": False}},
    }
    for rel, body in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(body))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["workloads"] = [
        {"name": "tiny-shortchat", "config": "tiny-jamba2",
         "traffic": "tiny-shortchat", "chips": 1, "why": "test"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (["tiny-shortchat"] if CELL in m["workloads"]
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
    body = harness.load_json("configs", "jamba2-3b.json")
    for key, value in PUBLISHED.items():
        assert body[key] == value, key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the catalog, where it is at hand
        with open(catalog) as f:
            row = next(json.loads(l) for l in f if "AI21-Jamba2-3B" in l)
        assert row["config"] == PUBLISHED
        assert row["source_url"] == body["source"]
    assert body["reduced"] == [] and "reduced_from" not in body
    assert (body["n_embd"], body["n_head"]) == (2560, 20)
    for key in ("layer_order", "experts", "inner_norms", "positions",
                "head_dim", "initializer_range"):
        assert key in body["assumed"], key
    assert len(body["departures"]) >= 3 and "whole" in body["deployment"]
    assert "3,029 M" in body["parameters"]["all"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = run.manifest_entry(manifest, "configs", "jamba2-3b")
    assert entry["source"] == body["source"] and entry["reduced"] == []
    cell = run.manifest_entry(manifest, "workloads", CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve-shortchat"
    reports = {m["name"] for m in run.metrics_of(manifest, "per_layer", CELL)}
    assert NEW | {"engine_iter_ms", "device_idle_pct.serve",
                  "chunk_step_ms.serve", "steps_ahead_pct.serve",
                  "paged_chunk_ms.serve", "device_step_ms.chunk.serve",
                  "setup_trace_s"} <= reports
    # (every step of the cell carries a chunk: none only decodes)
    assert not {"device_step_ms.decode.serve", "host_iter_ms.serve",
                "kda_state_ms.serve", "gqa_decode_ms.serve", "moe_ms.serve",
                "paged_decode_ms.serve", "prefix_hit_pct.serve"} & reports
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
    assert [m["name"] for m in run.metrics_of(
        manifest, "end_to_end", CELL)] == ["serve_tok_s", "setup_s"]


def test_the_arithmetic_is_the_built_models():
    """3.03 B parameters, attention at layers 7 and 21, 8.52 MB of h and
    0.80 MB of tails a slot, 1,024 B of keys and values a token: counted
    from the weight shapes the program declares for the configuration file
    (nothing is allocated)."""
    from flexflow_tpu.fftype import DataType, OperatorType as OT
    from flexflow_tpu.models import jamba_lm_config
    from flexflow_tpu.ops.attention import AttentionFrontEnd
    from flexflow_tpu.ops.base import BY_SLOT, get_op_def
    from flexflow_tpu.ops.ssm import SelectiveSSMDecodeParams

    body = harness.load_json("configs", "jamba2-3b.json")
    c = jamba_lm_config(body, sequence_length=128)
    assert [i for i, k in enumerate(c.layer_pattern) if k == "mha"] == [7, 21]
    assert set(c.layer_pattern) == {"mha", "mamba"} and c.tie_embeddings
    assert jamba2_reference.layer_kinds(body).count("attention") == 2
    assert jamba2_events.layers(body) == (2, 26)
    assert (c.position, c.num_kv_heads, c.head_dim, c.mlp) == (
        "none", 1, 128, "swiglu")
    d = c.hidden_size
    front = AttentionFrontEnd(d, c.num_heads, False, 0.0, False, 1e-6,
                              c.num_kv_heads, c.head_dim)

    def count(specs):
        return sum(math.prod(s.shape) for s in specs if s.trainable)

    softmax = count(front.weight_specs(d, d, d))
    mamba = count(c.mamba.weight_specs(d))
    mlp = 3 * d * c.intermediate_size
    assert round(mamba / 1e6, 1) == 41.2 and round(softmax / 1e6, 1) == 13.8
    assert round(mlp / 1e6, 1) == 62.9
    total = (26 * mamba + 2 * softmax + 28 * (mlp + 2 * d) + d
             + body["vocab_size"] * d)
    assert round(total / 1e9, 2) == 3.03 and round(total * 2 / 1e9, 2) == 6.06
    decode = SelectiveSSMDecodeParams(
        c.mamba, 256, 1536, cache_dtype=DataType.DT_BFLOAT16)
    state = get_op_def(OT.OP_SELECTIVE_SSM_DECODE).state(decode)
    assert state.bytes_of(BY_SLOT) == 16 * 5120 * 4 + 3 * 5120 * 2
    assert 26 * 16 * 5120 * 4 == jamba2_events.state_bytes_a_slot(body) \
        == 8_519_680
    assert state.step_counts([5, 9])["ssm_state_bytes"] == 4 * 327_680
    assert 26 * state.bytes_of(BY_SLOT) == 9_318_400
    assert jamba2_events.kv_bytes_a_row(body, 2) == 1024


def test_the_mix_and_the_cell_are_the_issues():
    mix = harness.load_json("traffic", "serve-shortchat.json")
    cell = harness.load_json("workloads", CELL + ".json")
    assert mix["kind"] == "closed_loop" and mix["cycle"] == 64
    assert mix["clients"] == 256 == cell["serve"]["slots"]
    assert mix["prompt_tokens"] == {"dist": "log_uniform", "min": 64,
                                    "max": 1024}
    assert mix["new_tokens"] == {"dist": "uniform", "min": 64, "max": 512}
    assert mix["check_prompt_tokens"] == [600]
    prompts, replies = traffic.request_sizes(mix)
    serve = cell["serve"]
    bs = serve["kv_block_size"]
    assert max(prompts) + max(replies) <= serve["max_seq_len"] == 1536
    assert serve["max_seq_len"] % bs == 0
    assert serve["prefill_chunk"] == 512
    assert serve["prefix_cache"] is False and cell["job"] == "serve_shortchat"
    # the pool holds every slot at the longest context at once
    assert serve["slots"] * serve["max_seq_len"] // bs < serve[
        "kv_num_blocks"]
    for key in ("prefill_chunk_why", "kv_block_size_why",
                "kv_num_blocks_why", "memory_why", "why"):
        assert cell[key], key
    assert "--dtype" in cell["flags"] and "bf16" in cell["flags"]


def test_the_benchmarks_reference_is_the_programs():
    mine = open(jamba2_reference.__file__).read()
    theirs = open(program_reference.__file__).read()
    assert mine == theirs
    for word in ("flexflow_tpu", "kernels", "ops.ssm", "import pallas"):
        assert word not in mine.split('"""', 2)[2], word
    job = harness.load_module("jobs", "serve_shortchat.py")
    assert 0 < job.LOGIT_TOL <= 0.1 and 0 < job.STATE_TOL < 1e-3
    assert 0.5 <= job.STATE_F32_SHARE < 1 and 0.6 <= job.SAME_SHARE < 0.85
    assert 0 < job.STATE_END_TOL < 0.36 and 0 < job.TAIL_TOL < 0.36


def test_shortchat_job_runs_a_window_through_serve(tiny, capsys):
    assert run.main(["--workload", "tiny-shortchat", "--seed", "3000000019",
                     "--seconds", "1.0", "--trace", "0"],
                    manifest_path=tiny) == 0
    out = capsys.readouterr().out
    line = result_line(out)
    assert line["correct"] is True and line["failed"] == 0, out
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert "slots reset for a new request" in out
    assert "ran in a reused slot" in out and "rows decoding a step" in out
    # the window's batch is replayed with every slot live, and float32
    # programs on one backend agree token for token
    assert "3 served streams replayed, every slot live: 100.00 %" in out
    assert "100.00 % of the stream that agrees least" in out


def _spoiled_run(tiny, capsys, seed):
    assert run.main(["--workload", "tiny-shortchat", "--seed", str(seed),
                     "--seconds", "0.5", "--trace", "0"],
                    manifest_path=tiny) == 0
    out = capsys.readouterr().out
    return result_line(out), out


def test_shortchat_job_is_not_correct_when_state_leaks_between_slots(
        tiny, capsys, monkeypatch):
    """The loop serves from a state that is another slot's (rows 0 and 1
    of every state-space layer's h change places before every step of the
    loop; the replay goes past `engine.step`): the replay's logits agree
    with the reference, the served tokens are not the replay's."""
    from flexflow_tpu.serving.engine import ServingEngine

    real = ServingEngine.step

    def step(engine):
        engine._complete_in_flight()
        state = engine.decode_model._state
        for name, leaves in state.items():
            if "state_h" in leaves:
                h = leaves["state_h"]
                state[name] = {**leaves, "state_h": h.at[:2].set(h[1::-1])}
        return real(engine)

    monkeypatch.setattr(ServingEngine, "step", step)
    line, out = _spoiled_run(tiny, capsys, 7)
    assert line["correct"] is False and line["failed"] >= 1, out
    assert "100.00 % of the stream" not in out


def test_shortchat_job_is_not_correct_when_the_state_passes_through_bf16(
        tiny, capsys, monkeypatch):
    """The program's state update rounds h to bfloat16 (the leaf stays
    float32): the kernel's check, and the engine's own leaves after the
    window, say so."""
    import jax

    from flexflow_tpu.kernels import selective_scan

    real = selective_scan.selective_scan_update

    def rounded(*args):
        y, state = real(*args)
        # (a cast there and back is the compiler's to fold on a TPU)
        return y, jax.lax.reduce_precision(state, exponent_bits=8,
                                           mantissa_bits=7)

    monkeypatch.setattr(selective_scan, "selective_scan_update", rounded)
    line, out = _spoiled_run(tiny, capsys, 9)
    assert line["correct"] is False, out
    assert "0.00 % of the slots' h is no bfloat16" in out


@pytest.mark.parametrize("spoil", ["no_inner_norms", "no_conv_bias", "no_d",
                                   "no_dt_bias"])
def test_shortchat_job_is_not_correct_when_the_logits_are_off(
        tiny, capsys, monkeypatch, spoil):
    """The float32 program against a reference that leaves a part of the
    layer out: outside the limit."""
    real = jamba2_reference.forward
    monkeypatch.setattr(
        jamba2_reference, "forward",
        lambda *a, **kw: real(*a, **{**kw, "spoil": spoil}))
    assert run.main(["--workload", "tiny-shortchat", "--seed", "5",
                     "--seconds", "0.5", "--trace", "0"],
                    manifest_path=tiny) == 0
    assert result_line(capsys.readouterr().out)["correct"] is False


def test_traced_shortchat_run_reads_what_it_can(tiny, capsys, monkeypatch):
    """--trace 1 with the trace steered to the recorded GPT-2 one (the CPU
    has no device plane): the job takes the text of the step programs it
    compiled for the scoped instructions, the readers find no step of this
    run in that trace and leave the device metrics out; the counters'
    metrics are there."""
    import jax

    with open(os.path.join(HERE, "recorded_trace.textproto")) as f:
        recorded = trace.read(
            jax.profiler.ProfileData.from_text_proto(f.read()))
    monkeypatch.setattr(trace, "read_file", lambda path: recorded)
    seen = {}
    scoped = jamba2_events.scoped_instructions
    monkeypatch.setattr(
        jamba2_events, "scoped_instructions",
        lambda text, bucket: seen.setdefault(bucket, scoped(text, bucket)))
    assert run.main(["--workload", "tiny-shortchat", "--seed", "1",
                     "--seconds", "1", "--trace", "1"], tiny) == 0
    out = capsys.readouterr().out
    line = result_line(out)
    assert line["correct"] is True, out
    assert {"engine_iter_ms", "chunk_step_ms.serve", "ffcompile_s",
            "xla_compile_s"} <= set(line["metrics"])
    assert not NEW & set(line["metrics"])
    # the step that only decodes, and a step a chunk bucket where chunks
    # ride as rows
    assert 0 in seen
    for bucket, triples in seen.items():
        assert {"ssm.proj", "ssm.conv", "ssm.param", "ssm.state",
                "ssm.out"} == {s for _, _, s in triples}, bucket
        assert {b for b, _, _ in triples} == {bucket}


BOTH = [[0, "fusion.7", "ssm.state"], [0, "fusion.9", "ssm.proj"],
        [64, "fusion.9", "ssm.state"], [64, "fusion.7", "ssm.proj"]]


def _hand_made_run(monkeypatch, triples=BOTH):
    """Two device steps that only decode (a projection, what feeds the
    kernel, the kernel once a state-space layer, the paged kernel once a
    softmax layer) around one with a chunk of bucket 64 (the kernel twice
    a layer; its program names the two fusions the other way round),
    joined to their spans by a `device_steps.sound` of the test's."""
    from benchmarks import device_steps

    body = harness.load_json("configs", "jamba2-3b.json")
    paged = "%flash_attention_paged_decode_grouped.1 = bf16[]"
    update = "%selective_scan_update.3 = f32[]"
    ops, steps, t0 = [], [], 10_000
    for kind in ("decode", "chunk", "decode"):
        at = t0
        for layer in range(28):
            if layer in (7, 21):
                ops.append((paged, at, at + 500))
                at += 1_000
                continue
            ops += [("%fusion.9 = bf16[]", at, at + 1_000),
                    ("%fusion.7 = f32[]", at + 1_000, at + 1_500),
                    (update, at + 1_500, at + 5_000)]
            at += 6_000
            if kind == "chunk":
                ops.append((update, at, at + 2_000))
                at += 3_000
        steps.append(device_steps.Step(
            id=len(steps), kind=kind, bucket=64 * (kind == "chunk"),
            chunk_start=0, rows=256, start=t0, end=at, busy_ns=at - t0,
            idle_before_ns=0,
            args={"state_rows": 200, "ssm_state_bytes": 2 * 200 * 327_680}))
        t0 = at + 3_000
    monkeypatch.setattr(
        device_steps, "sound",
        lambda run_: types.SimpleNamespace(steps=steps))
    return types.SimpleNamespace(
        result={"counters": {"jamba2_instructions": triples}
                if triples else {}},
        trace=types.SimpleNamespace(
            chips=[types.SimpleNamespace(ops=ops)], window=(0, 10**9)),
        config=body, peaks={"hbm_bytes_per_s": 8.19e11})


def test_jamba2_readers_on_hand_made_events(monkeypatch, capsys):
    """Every step is read, each by its own program's names: a layer's
    state update is 4.0 us in a step that only decodes and 4.5 in the
    chunk step, beside 2.0 of the chunk's scan."""
    run_ = _hand_made_run(monkeypatch)
    read = lambda name: harness.load_reader(name).read(run_)  # noqa: E731
    assert read("ssm_state_ms.serve") == pytest.approx(26 * 0.0125 / 3)
    assert read("ssm_mix_ms.serve") == pytest.approx(26 * 0.0025 / 3)
    assert read("mqa_decode_ms.serve") == pytest.approx(2 * 0.0005)
    assert read("ssm_scan_ms.serve") == pytest.approx(26 * 0.002)
    assert read("ssm_state_roofline_pct.serve") == pytest.approx(
        100 * 3 * 2 * 200 * 8_519_680 / 8.19e11 / (26 * 12.5e-6))
    out = capsys.readouterr().out
    assert "every step (1 with a chunk" in out
    assert "a step that only decodes: " in out


def test_jamba2_readers_pass_over_a_step_whose_text_was_not_left(
        monkeypatch):
    """Without the chunk program's triples its step is not read; nor is a
    chunk step whose kernel calls are not two a layer."""
    run_ = _hand_made_run(monkeypatch, triples=BOTH[:2])
    read = lambda name: harness.load_reader(name).read(run_)  # noqa: E731
    assert read("ssm_state_ms.serve") == pytest.approx(26 * 0.004)
    assert read("ssm_scan_ms.serve") is None
    from benchmarks import device_steps

    run_ = _hand_made_run(monkeypatch)
    chunk = device_steps.sound(run_).steps[1]
    ops = run_.trace.chips[0].ops
    ops.remove([o for o in ops if "selective_scan_update" in o[0]
                and chunk.start <= o[1] < chunk.end][-1])
    assert read("ssm_state_ms.serve") == pytest.approx(26 * 0.004)
    assert read("ssm_scan_ms.serve") is None


def test_jamba2_readers_return_none_from_an_empty_run(monkeypatch):
    run_ = _hand_made_run(monkeypatch, triples=None)
    for name in sorted(NEW):
        assert harness.load_reader(name).read(run_) is None, name
