"""The configuration, job, traffic, reference and readers of
`ms4-serve-longctx` (PR 46) on the CPU at tiny widths, as
test_mimo2_cell.py does it for PR 41's: the real sizes run only on the
chip. Nothing here reads the process-wide compile log or clears JAX's
caches: what a run compiled and when is the chip's to say.
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
        "benchmarks_run_ms4", os.path.join(REPO, "benchmarks", "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load_run()
from benchmarks import (  # noqa: E402
    device_steps, harness, mistral_small4_reference, ms4_events, trace,
    traffic,
)
from flexflow_tpu.models import (  # noqa: E402
    mistral_small4_lm_config, mistral_small4_reference as program_reference,
)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(CATALOG if os.path.exists(CATALOG) else os.devnull) as _f:
    _rows = [json.loads(line) for line in _f
             if '"Mistral-Small-4-119B-2603"' in line]
# the catalog row's config, key for key (kept here: the catalog is not
# part of the repository)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 0, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 12288,
    "kv_lora_rank": 256, "max_position_embeddings": 1048576,
    "mlp_bias": False, "model_type": "mistral4",
    "moe_intermediate_size": 2048, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 36, "num_key_value_heads": 32, "q_lora_rank": 1024,
    "qk_head_dim": 128, "qk_nope_head_dim": 64, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True,
    "rope_parameters": {
        "beta_fast": 32, "beta_slow": 1, "factor": 128,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 8192, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"},
    "routed_scaling_factor": 1, "sliding_window": None,
    "tie_word_embeddings": False, "topk_group": 1, "v_head_dim": 128,
    "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 6, "n_routed_experts": 16,
           "vocab_size": 16384}
# hidden 64, 4 heads, latent 32, rotary 8, a(t) stepping every 8
# positions; 4 of 16 experts of 24 held, 4 a token, a shared expert
TINY = {
    **PUBLISHED, "source": "the test file", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "qk_head_dim": 24, "head_dim": 24, "v_head_dim": 16, "n_embd": 64,
    "n_head": 4, "intermediate_size": 96, "moe_intermediate_size": 24,
    "rope_parameters": {**PUBLISHED["rope_parameters"],
                        "original_max_position_embeddings": 8},
    "num_hidden_layers": 3, "vocab_size": 97, "n_routed_experts": 4,
    "experts_held": [0, 4], "experts_routed": 16,
    "initializer_range": 0.1, "embedding_initializer_range": 0.5,
    "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
    "reduced_from": {"num_hidden_layers": 36, "n_routed_experts": 128,
                     "vocab_size": 131072}}
TINY_LONGCTX = {
    "kind": "closed_loop_sessions", "clients": 4, "cycle": 4,
    "history_tokens": {"dist": "log_uniform", "min": 10, "max": 30},
    "prompt_tokens": {"dist": "log_uniform", "min": 3, "max": 8},
    "new_tokens": {"dist": "uniform", "min": 2, "max": 5},
    "check_history_tokens": [11], "check_stream_histories": [0, 3]}
FLAGS = ["--mesh", "1,1,1,1", "--no-verify-plan"]
CELL = "ms4-serve-longctx"
CONFIG = "mistral-small-4-119b"
NAMES = ("mla_decode_ms.serve", "mla_decode_roofline_pct.serve")


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    files = {
        "configs/tiny-ms4.json": TINY,
        "traffic/tiny-longctx.json": TINY_LONGCTX,
        "workloads/tiny-longctx.json": {
            "job": "serve_longctx", "flags": FLAGS, "optimizer": "sgd",
            "attention_impl": "xla", "train_batch": 1,
            "train_sequence_length": 16, "trace_seconds": 1,
            "serve": {"slots": 4, "max_seq_len": 48, "prefill_chunk": 8,
                      "kv_layout": "paged", "kv_block_size": 4,
                      "kv_num_blocks": 96, "prefix_cache": True}},
    }
    for rel, body in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(body))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["workloads"] = [
        {"name": "tiny-longctx", "config": "tiny-ms4",
         "traffic": "tiny-longctx", "chips": 1, "why": "test"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (["tiny-longctx"] if CELL in m["workloads"]
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
    body = harness.load_json("configs", CONFIG + ".json")
    for key, value in PUBLISHED.items():
        assert body[key] == REDUCED.get(key, value), key
    if _rows:  # the catalog, where it is at hand
        assert _rows[0]["config"] == PUBLISHED
        assert _rows[0]["source_url"] == body["source"]
    assert body["reduced"] == list(REDUCED)
    assert body["reduced_from"] == {k: PUBLISHED[k] for k in REDUCED}
    assert (body["n_embd"], body["n_head"]) == (4096, 32)
    assert (body["experts_held"], body["experts_routed"]) == ([0, 16], 128)
    for key in ("query_scale", "router", "rope", "repeated_keys",
                "initializer_range", "embedding_initializer_range"):
        assert key in body["assumed"], key
    assert len(body["departures"]) >= 4 and "8 that share" in body[
        "deployment"]
    assert "12,288 is read by no layer" in body["intermediate_size_note"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = run.manifest_entry(manifest, "configs", CONFIG)
    assert entry["source"] == body["source"]
    assert entry["reduced"] == body["reduced"]
    cell = run.manifest_entry(manifest, "workloads", CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve-longctx"
    assert manifest["workloads"][-1] == cell
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    reports = {m["name"] for m in run.metrics_of(manifest, "per_layer", CELL)}
    assert {*NAMES, "moe_ms.serve", "prefix_hit_pct.serve",
            "chunk_step_ms.serve", "engine_iter_ms", "device_idle_pct.serve",
            "engine_idle_ms.schedule", "engine_idle_ms.stage",
            "engine_idle_ms.fetch", "steps_ahead_pct.serve",
            "device_step_ms.decode.serve", "device_step_ms.chunk.serve",
            "chunk_share_pct.serve", "host_iter_ms.serve",
            "host_stage_ms.serve", "step_join_pct.serve",
            "kv_bytes_a_token.serve", "ffcompile_s",
            "xla_compile_s"} == reports
    assert [m["name"] for m in run.metrics_of(
        manifest, "end_to_end", CELL)] == ["serve_tok_s", "setup_s"]


def test_the_parameter_table_is_the_programs_weight_shapes():
    """The configuration file's arithmetic against the shapes the ops
    declare for the published keys (no array is made)."""
    from flexflow_tpu.ops.moe import MoEMLPParams, _moe_mlp_weights

    body = harness.load_json("configs", CONFIG + ".json")
    c = mistral_small4_lm_config(body, sequence_length=128)
    assert (c.first_k_dense, c.num_layers, c.num_experts) == (0, 6, 128)
    assert c.latent.index is None
    assert c.latent.query_scale == (0.1, 8192)
    assert c.latent.rope_scaling == (128, 8192, 32, 1, 1)
    d = c.hidden_size
    attn = {w.name: math.prod(w.shape) for w in c.latent.weight_specs(d)
            if len(w.shape) == 2}
    assert round(sum(attn.values()) / 1e6, 2) == 28.05
    moe = {w.name: math.prod(w.shape) for w in _moe_mlp_weights(
        MoEMLPParams(c.num_experts, c.num_experts_per_tok,
                     c.moe_intermediate_size, **c.moe_routing),
        [(16, 1, d)]) if w.trainable}
    assert moe["router"] == 4096 * 128 and "router_bias" not in moe
    assert moe["gate"] + moe["up"] + moe["down"] == 16 * 3 * 4096 * 2048
    shared = sum(moe[n] for n in ("shared_gate", "shared_up", "shared_down"))
    assert shared == 3 * 4096 * 2048
    a_layer = sum(attn.values()) + sum(moe.values())
    assert round(a_layer / 1e6, 1) == 456.4
    whole = 6 * a_layer + 2 * c.vocab_size * d
    assert round(whole / 1e6, 1) == 2872.6
    assert "2,872.6 M" in body["parameters"]["all"]
    # uncut, the same equations count the published 119 B
    uncut = 36 * (a_layer - moe["gate"] - moe["up"] - moe["down"]
                  + 128 * 3 * 4096 * 2048) + 2 * 131072 * d
    assert round(uncut / 1e9, 1) == 119.0
    # the cache: one latent row a layer in whole 128-lane tiles
    assert c.latent.cache_row_widths == {"pool_c": 384}
    assert ms4_events.latent_bytes_a_row(body, 2) == 6 * 640 == 3840
    assert ms4_events.kernel_flops_a_row(body) == 6 * 2 * 32 * (320 + 256)
    assert "4,608 B" in body["parameters"]["cache_a_token"]


def test_the_mix_and_the_cell_are_the_issues():
    mix = harness.load_json("traffic", "serve-longctx.json")
    cell = harness.load_json("workloads", CELL + ".json")
    assert mix["kind"] == "closed_loop_sessions"
    assert set(mix) >= set(harness.load_json("traffic",
                                             "serve-sessions.json"))
    histories = traffic.quantiles(mix["history_tokens"], mix["clients"])
    assert len(histories) == 16 == mix["cycle"] == cell["serve"]["slots"]
    assert 16384 <= min(histories) and max(histories) <= 65536
    assert 560_000 < sum(histories) < 575_000
    assert histories == sorted(histories) and len(set(histories)) == 16
    questions, replies = traffic.request_sizes(mix)
    assert 32 <= min(questions) and max(questions) <= 128
    assert 128 <= min(replies) and max(replies) <= 512
    # the two compared streams' contexts: near 17 k (a(t) has taken two
    # steps) and 41 k (five)
    near = [histories[c] for c in mix["check_stream_histories"]]
    assert 2 * 8192 < near[0] < 3 * 8192 and 4 * 8192 < near[1] < 5 * 8192
    serve = cell["serve"]
    assert serve["kv_layout"] == "paged" and serve["prefix_cache"] is True
    assert (serve["kv_block_size"], serve["prefill_chunk"]) == (256, 256)
    assert serve["max_seq_len"] >= (max(histories) + max(questions)
                                    + max(replies))
    blocks = sum(-(-h // 256) for h in histories)
    # the histories, four blocks a live request, and room for tails
    assert blocks + 16 * 4 + 200 <= serve["kv_num_blocks"]
    assert "--dtype" in cell["flags"] and "bf16" in cell["flags"]
    assert cell["job"] == "serve_longctx" and cell["trace_seconds"] == 4


def test_the_benchmarks_reference_is_the_programs():
    mine = open(mistral_small4_reference.__file__).read()
    theirs = open(program_reference.__file__).read()
    head = "`build_transformer_lm` builds it from `mistral_small4_lm_config`"
    body = theirs[theirs.index("float32, `jax.default_matmul"):]
    body = body.replace("from . import deepseek_v32_reference as dsa",
                        "from benchmarks import deepseek_v32_reference "
                        "as dsa")
    assert head in mine and body in mine
    assert 0 < mistral_small4_reference.LOGIT_TOL < 0.1
    assert 0 < mistral_small4_reference.ROUTE_MARGIN < 0.3
    assert 0 < mistral_small4_reference.ATTEND_TOL < 0.05
    assert 0 < mistral_small4_reference.LAYER_ATTEND_TOL < 0.05
    assert 0 < mistral_small4_reference.CACHE_TOL < 0.05
    assert mistral_small4_reference.SPOILS == program_reference.SPOILS
    assert {"query_scale_off", "yarn_off", "rope_half_pairing", "renorm_off",
            "shared_off", "e4m3"} <= set(program_reference.SPOILS)


def test_longctx_job_runs_a_window_through_serve(tiny, capsys):
    assert run.main(["--workload", "tiny-longctx", "--seed",
                     str(2**31 + 11), "--seconds", "1.5", "--trace", "0"],
                    tiny) == 0
    out = capsys.readouterr().out
    line = result_line(out)
    assert line["correct"] is True and line["failed"] == 0, out
    assert "prompt tokens: sound" in out
    assert "0 without their whole history" in out
    assert "0 histories moved or evicted" in out and "0 dropped" in out
    assert out.count("replayed with 4 slots live") == 2
    # every layer's attention output at the decoded rows of the check
    # prompt and of the two compared streams, from the step's own program
    assert out.count("the layers' attention outputs, from the step's own "
                     "program") == 3
    assert "is off the reference's, or not all 3 sequences" not in out
    # the experts the prompts' rows chose, chunk by chunk, go to the
    # reference beside the decoded rows'
    assert "chose at 11 prompt positions of 19 tokens" in out
    assert out.count("the reference is given the experts") == 3
    assert "chose at 0 prompt positions" not in out
    # the first layer's attention and cache rows of the two compared
    # sessions, float32 against float32
    assert out.count("the first layer of a served prompt") == 2
    assert "off the reference's, or not all held" not in out
    assert line["attempted"] >= 4
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("control", ["query_scale_off", "lost_block"])
def test_longctx_job_is_not_correct_under_a_control(tiny, capsys,
                                                    monkeypatch, control):
    """The builder's controls through the job's own hook: a spoil of the
    reference (every entry moves the logits: tests/test_mistral_small4.py),
    and the replay with one cached block of every history zeroed."""
    load = harness.load_module

    def loaded(*parts):
        module = load(*parts)
        if parts == ("jobs", "serve_longctx.py"):
            job = module.run
            module.run = lambda ctx: job(ctx, control=control)
        return module

    monkeypatch.setattr(harness, "load_module", loaded)
    argv = ["--workload", "tiny-longctx", "--seed", "5", "--seconds", "0.3",
            "--trace", "0"]
    # the CPU lays a chunk step out as a rectangle, where the replay does
    # not feed a stream's first token (on the chip a chunk rides as rows
    # and it does): a question prefilled over a zeroed block may sample
    # another first token than the loop served, which the replay refuses
    # outright
    try:
        assert run.main(argv, tiny) == 0
    except RuntimeError as e:
        assert control == "lost_block"
        assert "not fed the served stream" in str(e)
    else:
        out = capsys.readouterr().out
        assert result_line(out)["correct"] is False
        # the replayed step's own record of every layer's attention tells
        # either, and the first layer's, read alone
        assert "a layer's attention output of a compared sequence is off" in out
        assert "attention or cache rows of the compared sessions are off" in out


def test_traced_longctx_run_reads_what_it_can(tiny, capsys, monkeypatch):
    """--trace 1 with the trace steered to the recorded GPT-2 one (the CPU
    has no device plane): the job compiles the decode step's text for the
    scoped instructions of both readers' modules; the readers find no step
    in that trace and leave the device metrics out; the counters' metrics
    are there."""
    import jax

    with open(os.path.join(HERE, "recorded_trace.textproto")) as f:
        recorded = trace.read(
            jax.profiler.ProfileData.from_text_proto(f.read()))
    monkeypatch.setattr(trace, "read_file", lambda path: recorded)
    seen = {}
    scoped = ms4_events.scoped_instructions
    monkeypatch.setattr(
        ms4_events, "scoped_instructions",
        lambda text: seen.setdefault("pairs", scoped(text)))
    assert run.main(["--workload", "tiny-longctx", "--seed", "1",
                     "--seconds", "30", "--trace", "1"], tiny) == 0
    out = capsys.readouterr().out
    line = result_line(out)
    assert line["correct"] is True, out
    assert {"prefix_hit_pct.serve", "engine_iter_ms", "chunk_step_ms.serve",
            "kv_bytes_a_token.serve", "ffcompile_s",
            "xla_compile_s"} <= set(line["metrics"])
    # a latent row of 32 + 8 numbers in one 128-lane tile, float32, 3 layers
    assert line["metrics"]["kv_bytes_a_token.serve"]["value"] == 3 * 128 * 4
    assert not set(NAMES) & set(line["metrics"])
    assert line["metrics"]["prefix_hit_pct.serve"]["value"] > 50
    assert {"mla.q", "mla.kv", "mla.attend", "mla.out", "moe.route",
            "moe.experts", "moe.combine", "moe.shared"} <= {
                s for _, s in seen["pairs"]}


HLO = '''
  %fusion.1 = bf16[16,32,384]{2,1,0} fusion(%p), kind=kOutput, calls=%f.1, metadata={op_name="jit(decode_step)/l0_attn/mla.q/concatenate"}
  %paged_latent_decode.2 = bf16[16,32,256]{2,1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_step)/l0_attn/mla.attend/paged_latent_decode/pallas_call"}
  %fusion.3 = bf16[16,32,128]{2,1,0} fusion(%z), kind=kOutput, calls=%f.3, metadata={op_name="jit(decode_step)/l0_attn/mla.attend/dot_general"}
  %fusion.4 = bf16[16,4096]{1,0} fusion(%z), kind=kOutput, calls=%f.4, metadata={op_name="jit(decode_step)/l0_moe/moe.shared/mul"}
  ROOT %fusion.5 = bf16[16,16384]{1,0} fusion(%z), kind=kOutput, calls=%f.5, metadata={op_name="jit(decode_step)/lm_head/dot_general"}
'''


def hand_made_run(pairs, steps):
    ms = 1_000_000
    ops = [("%fusion.1 = bf16[16,32,384] fusion(%p)", 0, 1 * ms),
           ("%paged_latent_decode.2 = bf16[16,32,256] custom-call(%p)",
            1 * ms, 5 * ms),
           ("%fusion.3 = bf16[16,32,128] fusion(%z)", 5 * ms, 6 * ms),
           ("%fusion.4 = bf16[16,4096] fusion(%z)", 6 * ms, 8 * ms),
           ("%fusion.5 = bf16[16,16384] fusion(%z)", 8 * ms, 10 * ms),
           # the second step: longer contexts
           ("%paged_latent_decode.2 = bf16[16,32,256] custom-call(%p)",
            20 * ms, 28 * ms),
           ("%fusion.3 = bf16[16,32,128] fusion(%z)", 28 * ms, 29 * ms),
           # a chunk step's events: another step's interval, left out
           ("%paged_latent_decode.2 = bf16[16,32,256] custom-call(%p)",
            40 * ms, 49 * ms)]
    r = types.SimpleNamespace(
        result={"counters": {"ms4_instructions": pairs}},
        config=harness.load_json("configs", CONFIG + ".json"),
        peaks={"hbm_bytes_per_s": 8.0e11, "bf16_flops_per_s": 2.0e14})
    r.trace = trace.Trace([trace.Chip(0, ops, [])], [], (0, 60 * ms))
    r.device_steps = device_steps.Record(
        steps, len(steps), {}, 0.0, (0.0, 0.0), [], [])
    return r


def a_step(i, kind, start, end, **args):
    ms = 1_000_000
    return device_steps.Step(
        id=i, kind=kind, bucket=0, chunk_start=0, rows=16, start=start * ms,
        end=end * ms, busy_ns=0.0, idle_before_ns=0.0, args=args)


def test_the_new_readers_on_hand_made_events(capsys):
    """Two pure-decode steps and a chunk step: the readers take the events
    inside the device's own intervals of the decode steps, the scope's for
    the milliseconds and the kernel's own for the share, and hold the
    kernel to the published latent rows the steps' arguments count."""
    pairs = ms4_events.scoped_instructions(HLO)
    assert pairs == [["fusion.1", "mla.q"],
                     ["paged_latent_decode.2", "mla.attend"],
                     ["fusion.3", "mla.attend"], ["fusion.4", "moe.shared"]]
    steps = [a_step(1, "decode", 0, 10, kv_itemsize=2, kv_rows=400_000),
             a_step(2, "decode", 20, 30, kv_itemsize=2, kv_rows=800_000),
             a_step(3, "chunk", 40, 50, kv_itemsize=2, kv_rows=800_000)]
    r = hand_made_run(pairs, steps)
    read = lambda name: harness.load_reader(name).read(r)  # noqa: E731
    # mla.attend: the kernel's 4 + 8 ms and W_uv's 1 + 1, over two steps
    assert read(NAMES[0]) == pytest.approx(7.0)
    found = ms4_events.by_scope(r)
    assert found["kernel"] == pytest.approx(12e-3)
    assert found["other"] == pytest.approx(2e-3) and len(found["steps"]) == 2
    # by hand: 1,200,000 rows x 6 layers x 640 B = 4,608,000,000 B at
    # 8e11 B/s = 5.76 ms, over the kernel's 12 ms
    assert read(NAMES[1]) == pytest.approx(100 * 4_608_000_000 / 8e11 / 12e-3)
    assert 0 < read(NAMES[1]) < 100
    # the FLOPs' share of the MXU's peak is printed beside it, not a metric
    assert "% of the MXU's peak" in capsys.readouterr().out


def test_the_new_readers_find_nothing_on_a_parent_or_a_bad_join():
    pairs = ms4_events.scoped_instructions(HLO)
    steps = [a_step(1, "decode", 0, 10, kv_itemsize=2, kv_rows=1000)]
    # a program without the kernel: the scope's milliseconds, no share
    no_kernel = hand_made_run(
        [[n, s] for n, s in pairs if not n.startswith("paged")], steps)
    no_kernel.trace = trace.Trace([trace.Chip(0, [
        ("%fusion.3 = bf16[16,32,128] fusion(%z)", 0, 2_000_000)], [])], [],
        (0, 60_000_000))
    assert harness.load_reader(NAMES[0]).read(no_kernel) == pytest.approx(2.0)
    assert harness.load_reader(NAMES[1]).read(no_kernel) is None
    no_pairs = hand_made_run(None, steps)
    unjoined = hand_made_run(pairs, steps)
    unjoined.device_steps.dispatched = 2    # one step was not joined
    nothing = hand_made_run(pairs, steps)
    nothing.device_steps = None             # a program without `step` ids
    for r in (no_pairs, unjoined, nothing):
        for name in NAMES:
            assert harness.load_reader(name).read(r) is None, name
