"""The configuration, job, traffic, reference and readers of
`solar2-serve-reason` (PR 33) on the CPU at tiny widths, as
test_dsv32_cell.py does it for PR 31's: the real sizes run only on the
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
        "benchmarks_run_solar2", os.path.join(REPO, "benchmarks", "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load_run()
from benchmarks import (  # noqa: E402
    harness, solar2_events, solar_open2_reference, trace, traffic,
)
from flexflow_tpu.models import (  # noqa: E402
    solar_open2_reference as program_reference,
)

# the catalog row's config, key for key (kept here: the catalog is not
# part of the repository)
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "tie_word_embeddings": False, "max_position_embeddings": 1048576,
    "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}
REDUCED = {"num_hidden_layers": 4, "n_routed_experts": 40,
           "vocab_size": 24576}
TINY = {
    **PUBLISHED, "source": "the test file", "hidden_size": 64,
    "num_attention_heads": 4, "head_dim": 16, "num_key_value_heads": 2,
    "n_embd": 64, "n_head": 4, "num_hidden_layers": 4,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                           "num_heads": 4, "num_kv_heads": None},
    "moe_intermediate_size": 24, "n_routed_experts": 4,
    "experts_held": [4, 4], "experts_routed": 16, "num_experts_per_tok": 4,
    "vocab_size": 97, "initializer_range": 0.1,
    "reduced": ["n_routed_experts"], "reduced_from": {"n_routed_experts": 16}}
TINY_REASON = {
    "kind": "closed_loop", "clients": 3, "cycle": 3,
    "prompt_tokens": {"dist": "log_uniform", "min": 5, "max": 20},
    "new_tokens": {"dist": "uniform", "min": 3, "max": 9},
    "check_prompt_tokens": [13]}
FLAGS = ["--mesh", "1,1,1,1", "--no-verify-plan"]


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    files = {
        "configs/tiny-solar2.json": TINY,
        "traffic/tiny-reason.json": TINY_REASON,
        "workloads/tiny-reason.json": {
            "job": "serve_reason", "flags": FLAGS, "optimizer": "sgd",
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
        {"name": "tiny-reason", "config": "tiny-solar2",
         "traffic": "tiny-reason", "chips": 1, "why": "test"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (["tiny-reason"]
                              if "solar2-serve-reason" in m["workloads"]
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


def test_configuration_carries_every_published_width():
    body = harness.load_json("configs", "solar-open2-250b.json")
    for key, value in PUBLISHED.items():
        assert body[key] == REDUCED.get(key, value), key
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the catalog, where it is at hand
        with open(catalog) as f:
            row = next(json.loads(l) for l in f if "Solar-Open2-250B" in l)
        assert row["config"] == PUBLISHED
        assert row["source_url"] == body["source"]
    assert body["reduced"] == list(REDUCED)
    assert body["reduced_from"] == {k: PUBLISHED[k] for k in REDUCED}
    assert body["experts_held"] == [0, 40] and body["experts_routed"] == 320
    assert (body["n_embd"], body["n_head"]) == (4096, 64)
    for key in ("router", "shared_expert_width", "gqa_gate", "kda_low_rank",
                "biases", "qk_norm", "conv_bias", "state_dtype",
                "initializer_range"):
        assert key in body["assumed"], key
    assert len(body["departures"]) >= 3 and "8 chips" in body["deployment"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = run.manifest_entry(manifest, "configs", "solar-open2-250b")
    assert entry["source"] == body["source"]
    assert entry["reduced"] == body["reduced"]
    cell = run.manifest_entry(manifest, "workloads", "solar2-serve-reason")
    assert cell["chips"] == 1 and cell["traffic"] == "serve-reason"
    reports = {m["name"] for m in run.metrics_of(
        manifest, "per_layer", "solar2-serve-reason")}
    assert {"kda_state_ms.serve", "kda_state_roofline_pct.serve",
            "kda_mix_ms.serve", "gqa_decode_ms.serve",
            "gqa_decode_roofline_pct.serve", "moe_ms.serve",
            "engine_iter_ms", "device_idle_pct.serve", "chunk_step_ms.serve",
            "steps_ahead_pct.serve", "engine_idle_ms.fetch"} <= reports
    assert not {"paged_decode_ms.serve", "paged_decode_roofline_pct.serve",
                "prefill_share_pct"} & reports
    assert [m["name"] for m in run.metrics_of(
        manifest, "end_to_end", "solar2-serve-reason")] == [
            "serve_tok_s", "setup_s"]


def test_the_arithmetic_of_the_cut_is_the_built_models():
    """3,308 M parameters, 12.58 MB of state a slot, 4,096 B of keys and
    values a token: counted from the weight shapes the program declares
    for the configuration file (nothing is allocated)."""
    from flexflow_tpu.fftype import OperatorType as OT
    from flexflow_tpu.models import solar_open2_lm_config
    from flexflow_tpu.ops import MoEMLPParams
    from flexflow_tpu.ops.attention import AttentionFrontEnd
    from flexflow_tpu.ops.base import get_op_def
    from flexflow_tpu.ops.delta_attention import GatedDeltaDecodeParams

    body = harness.load_json("configs", "solar-open2-250b.json")
    c = solar_open2_lm_config(body, sequence_length=128)
    assert c.layer_pattern == ("mha", "delta", "delta", "delta")
    d, x = c.hidden_size, (1, 1, c.hidden_size)
    front = AttentionFrontEnd(d, c.num_heads, False, 0.0, False, 1e-5,
                              c.num_kv_heads, c.head_dim, c.attention_gate)
    moe = MoEMLPParams(c.num_experts, c.num_experts_per_tok,
                       c.moe_intermediate_size, **c.moe_routing)
    decode = GatedDeltaDecodeParams(c.delta, 1, 4352)

    def count(specs, trainable=True):
        return sum(math.prod(s.shape) for s in specs
                   if s.trainable == trainable)

    softmax = count(front.weight_specs(d, d, d))
    delta = count(c.delta.weight_specs(d))
    experts = count(get_op_def(OT.OP_MOE_MLP).weights(moe, [x]))
    held = 40 * 3 * d * 1280
    assert round(softmax / 1e6, 1) == 109.1
    assert round(delta / 1e6, 1) == 137.7
    assert round((experts - held + 2 * d) / 1e6, 1) == 17.0
    total = (softmax + 3 * delta + 4 * (experts + 2 * d) + d
             + 2 * body["vocab_size"] * d)
    assert round(total / 1e6) == 3308
    assert round(total * 2 / 1e9, 2) == 6.62
    state = 3 * math.prod(decode.state_leaves["state_s"]) * 4
    assert state == solar2_events.state_bytes_a_slot(body) == 12_582_912
    assert 3 * math.prod(decode.state_leaves["state_conv"]) * 2 == 442_368
    assert 2 * front.kv_width * 2 == 4096 == solar2_events.kv_bytes_a_row(
        body, 2)


def test_the_mix_and_the_cell_are_the_issues():
    mix = harness.load_json("traffic", "serve-reason.json")
    cell = harness.load_json("workloads", "solar2-serve-reason.json")
    assert mix["kind"] == "closed_loop" and mix["cycle"] == 32
    assert mix["clients"] == 128 == cell["serve"]["slots"]
    assert mix["prompt_tokens"] == {"dist": "log_uniform", "min": 128,
                                    "max": 2048}
    assert mix["new_tokens"] == {"dist": "uniform", "min": 512, "max": 2048}
    prompts, replies = traffic.request_sizes(mix)
    serve = cell["serve"]
    assert max(prompts) + max(replies) <= 4096 < serve["max_seq_len"] == 4352
    assert serve["prefill_chunk"] == 256 == serve["kv_block_size"]
    assert serve["prefix_cache"] is False and cell["job"] == "serve_reason"
    # the pool holds 128 live requests however the seed pairs the lengths
    # of a cycle: the largest prompts with the largest replies
    bs = serve["kv_block_size"]
    need = 4 * sum(-(-(p + r) // bs) for p, r in zip(prompts, replies))
    assert need < serve["kv_num_blocks"] == 1600
    assert cell["kv_block_size_why"] and cell["kv_num_blocks_why"]
    assert "--dtype" in cell["flags"] and "bf16" in cell["flags"]


def test_the_benchmarks_reference_is_the_programs():
    mine = open(solar_open2_reference.__file__).read()
    theirs = open(program_reference.__file__).read()
    assert mine == theirs
    job = harness.load_module("jobs", "serve_reason.py")
    assert 0 < job.LOGIT_TOL <= 0.1 and 0 < job.ROUTE_MARGIN <= 0.2
    assert 0 < job.STATE_TOL < 1e-3 and 0 < job.ROUTE_BAD_SHARE < 0.01
    assert 0.5 <= job.STATE_F32_SHARE < 1 and 0.85 <= job.SAME_SHARE < 0.94
    assert 0.05 < job.STATE_END_TOL < 0.36


def test_reason_job_runs_a_window_through_serve(tiny, capsys):
    assert run.main(["--workload", "tiny-reason", "--seed", "3000000019",
                     "--seconds", "1.0", "--trace", "0"],
                    manifest_path=tiny) == 0
    out = capsys.readouterr().out
    line = result_line(out)
    assert line["correct"] is True and line["failed"] == 0, out
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert "slots reset for a new request" in out
    assert "ran in a reused slot" in out
    # the window's batch is replayed with every slot live, and float32
    # programs on one backend agree token for token
    assert "3 served streams replayed, every slot live: 100.00 %" in out
    assert "100.00 % of the stream that agrees least" in out


def _spoiled_run(tiny, capsys, seed):
    assert run.main(["--workload", "tiny-reason", "--seed", str(seed),
                     "--seconds", "0.5", "--trace", "0"],
                    manifest_path=tiny) == 0
    out = capsys.readouterr().out
    return result_line(out), out


def test_reason_job_is_not_correct_when_state_leaks_between_slots(
        tiny, capsys, monkeypatch):
    """The loop serves from a state that is another slot's (rows 0 and 1
    of every delta-rule layer's state change places before every step of
    the loop; the replay goes past `engine.step`): the replay's logits
    agree with the reference, the served tokens are not the replay's."""
    from flexflow_tpu.serving.engine import ServingEngine

    real = ServingEngine.step

    def step(engine):
        engine._complete_in_flight()
        state = engine.decode_model._state
        for name, leaves in state.items():
            if "state_s" in leaves:
                S = leaves["state_s"]
                state[name] = {**leaves, "state_s": S.at[:2].set(S[1::-1])}
        return real(engine)

    monkeypatch.setattr(ServingEngine, "step", step)
    line, out = _spoiled_run(tiny, capsys, 7)
    assert line["correct"] is False and line["failed"] >= 1, out
    assert "0 lie beyond" in out and "100.00 % of the stream" not in out


def test_reason_job_is_not_correct_when_the_state_passes_through_bf16(
        tiny, capsys, monkeypatch):
    """The program's state update rounds the state to bfloat16 (the leaf
    stays float32): the kernel's check, and the engine's own leaves after
    the window, say so."""
    import jax

    from flexflow_tpu.kernels import delta_rule

    real = delta_rule.delta_rule_update

    def rounded(*args):
        o, state = real(*args)
        # (a cast there and back is the compiler's to fold on a TPU)
        return o, jax.lax.reduce_precision(state, exponent_bits=8,
                                           mantissa_bits=7)

    monkeypatch.setattr(delta_rule, "delta_rule_update", rounded)
    line, out = _spoiled_run(tiny, capsys, 9)
    assert line["correct"] is False, out
    assert "0.00 % of the slots' state is no bfloat16" in out


def test_reason_job_is_not_correct_when_the_logits_are_off(
        tiny, capsys, monkeypatch):
    """The float32 program against a reference whose beta lacks its
    factor 2: outside the limit."""
    real = solar_open2_reference.forward
    monkeypatch.setattr(
        solar_open2_reference, "forward",
        lambda *a, **kw: real(*a, **{**kw, "spoil": "beta1"}))
    assert run.main(["--workload", "tiny-reason", "--seed", "5",
                     "--seconds", "0.5", "--trace", "0"],
                    manifest_path=tiny) == 0
    assert result_line(capsys.readouterr().out)["correct"] is False


def test_traced_reason_run_reads_what_it_can(tiny, capsys, monkeypatch):
    """--trace 1 with the trace steered to the recorded GPT-2 one (the CPU
    has no device plane): the job compiles the decode step's text for the
    scoped instructions of both readers' helpers, the readers find no
    `ff/serve.step` span in that trace and leave the device metrics out;
    the counters' metrics are there."""
    import jax

    with open(os.path.join(HERE, "recorded_trace.textproto")) as f:
        recorded = trace.read(
            jax.profiler.ProfileData.from_text_proto(f.read()))
    monkeypatch.setattr(trace, "read_file", lambda path: recorded)
    seen = {}
    scoped = solar2_events.scoped_instructions
    monkeypatch.setattr(
        solar2_events, "scoped_instructions",
        lambda text: seen.setdefault("pairs", scoped(text)))
    assert run.main(["--workload", "tiny-reason", "--seed", "1",
                     "--seconds", "1", "--trace", "1"], tiny) == 0
    out = capsys.readouterr().out
    line = result_line(out)
    assert line["correct"] is True, out
    assert {"engine_iter_ms", "chunk_step_ms.serve", "ffcompile_s",
            "xla_compile_s"} <= set(line["metrics"])
    assert not {"kda_state_ms.serve", "gqa_decode_roofline_pct.serve"} & set(
        line["metrics"])
    assert {"kda.proj", "kda.conv", "kda.gate", "kda.state", "kda.out",
            "gqa.attend"} <= {s for _, s in seen["pairs"]}


def test_solar2_readers_on_hand_made_events():
    """Device steps cut at the paged kernel's events: two that only decode
    (the state kernel once a delta-rule layer, its feeding op, a
    projection) around one with a chunk (the state kernel twice a layer),
    lying a little before the host's spans as with a step in flight; the
    readers' milliseconds a pure-decode step, and the roofline shares from
    the bytes functions and the spans' counts."""
    body = harness.load_json("configs", "solar-open2-250b.json")
    paged = "%flash_attention_paged_decode_grouped.1 = bf16[]"
    update = "%delta_rule_update.3 = f32[]"
    ops, t0 = [], 10_000
    for chunk in (False, True, False, False):   # the last is cut off
        ops.append((paged, t0, t0 + 1_000))
        at = t0 + 2_000
        for _ in range(6 if chunk else 3):
            ops += [("%fusion.9 = bf16[]", at, at + 1_000),
                    ("%fusion.7 = f32[]", at + 1_000, at + 1_500),
                    (update, at + 1_500, at + 5_000)]
            at += 6_000
        t0 = at + 3_000
    spans = [("ff/serve.step", 15_000 + 30_000 * i, 40_000 + 30_000 * i,
              {"state_rows": 128, "kv_rows": 100_000, "kv_itemsize": 2})
             for i in range(2)]
    run_ = types.SimpleNamespace(
        result={"counters": {"solar2_instructions": [
            ["fusion.7", "kda.state"], ["fusion.9", "kda.proj"]]}},
        trace=types.SimpleNamespace(
            chips=[types.SimpleNamespace(ops=ops)], window=(0, 500_000)),
        program_spans=spans, config=body,
        peaks={"hbm_bytes_per_s": 8.19e11})
    read = lambda name: harness.load_reader(name).read(run_)  # noqa: E731
    steps = solar2_events.device_steps(run_)
    assert [decodes for _, decodes in steps] == [True, False, True]
    assert read("kda_state_ms.serve") == pytest.approx(3 * 0.004)
    assert read("kda_mix_ms.serve") == pytest.approx(3 * 0.001)
    assert read("gqa_decode_ms.serve") == pytest.approx(0.001)
    assert read("kda_state_roofline_pct.serve") == pytest.approx(
        100 * 2 * 128 * 12_582_912 / 8.19e11 / 12e-6)
    assert read("gqa_decode_roofline_pct.serve") == pytest.approx(
        100 * 100_000 * 4096 / 8.19e11 / 1e-6)
    run_.result["counters"] = {}
    del run_.solar2_by_scope
    assert read("kda_state_ms.serve") is None
    assert read("gqa_decode_roofline_pct.serve") is None
