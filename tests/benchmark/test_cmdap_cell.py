"""The configuration, job, traffic, reference and readers of
`cmdap-serve-agentmix` (PR 49) on the CPU at tiny widths, as
test_mimo2_cell.py does it for PR 41's: the real sizes run only on the
chip. Nothing here reads the process-wide compile log: what a run compiled
and when is the chip's to say.
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
        "benchmarks_run_cmdap", os.path.join(REPO, "benchmarks", "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load_run()
from benchmarks import (  # noqa: E402
    cmdap_events, command_a_plus_reference, device_steps, harness,
    mimo2_events, trace, traffic,
)
from flexflow_tpu.models import (  # noqa: E402
    command_a_plus_lm_config, command_a_plus_reference as program_reference,
)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(CATALOG if os.path.exists(CATALOG) else os.devnull) as _f:
    _rows = [json.loads(line) for line in _f
             if '"command-a-plus-05-2026"' in line]
# the catalog row's config, key for key (kept here: the catalog is not
# part of the repository)
PUBLISHED = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
    "layer_switch": 4,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8,
    "logit_scale": 1, "max_position_embeddings": 200000,
    "model_type": "cohere2_moe", "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 8, "num_shared_experts": 4,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average", "sliding_window": 4096,
    "tf_legacy_loss": False, "tie_word_embeddings": True,
    "use_embedding_sharing": True, "use_gated_activation": True,
    "use_parallel_block": True, "use_parallel_embedding": False,
    "use_qk_norm": False, "vocab_size": 262144}
REDUCED = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 32768}
# hidden 64; 8 query heads of 16 on 2 KV heads, a window of 16; layers
# [window, window, window, global]; 4 of 16 experts of 24 held, 4 a token,
# beside 4 shared experts
TINY = {
    **PUBLISHED, "source": "the test file", "hidden_size": 64,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "n_embd": 64, "n_head": 8, "intermediate_size": 24, "sliding_window": 16,
    "num_hidden_layers": 4, "vocab_size": 509, "num_experts": 4,
    "experts_held": [0, 4], "experts_routed": 16, "num_experts_per_tok": 4,
    "hybrid_layer_pattern": [1, 1, 1, 0], "swa_num_key_value_heads": 2,
    "swa_head_dim": 16, "swa_v_head_dim": 16, "v_head_dim": 16,
    "initializer_range": 0.1, "embedding_initializer_range": 0.1,
    "embedding_initializer_mean": 0.3,
    "reduced": ["num_hidden_layers", "num_experts", "vocab_size"],
    "reduced_from": {"num_hidden_layers": 32, "num_experts": 128,
                     "vocab_size": 262144}}
# histories of 5, 9, 16 and 27 over a window of 16: sessions 0 and 1 are
# under it and may cross it inside a request
TINY_AGENTMIX = {
    "kind": "closed_loop_sessions", "clients": 4, "cycle": 4,
    "history_tokens": {"dist": "log_uniform", "min": 4, "max": 36},
    # (turns of one bucket, 8, and so are they where a turn's first token
    # happens to continue a cached tail: a vocabulary of 509 makes that
    # likely in a window of hundreds of requests)
    "prompt_tokens": {"dist": "log_uniform", "min": 6, "max": 8},
    "new_tokens": {"dist": "uniform", "min": 2, "max": 5},
    "check_history_tokens": [21], "check_stream_histories": [0, 3]}
FLAGS = ["--mesh", "1,1,1,1", "--no-verify-plan"]
CELL = "cmdap-serve-agentmix"
CONFIG = "command-a-plus-05-2026"


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    files = {
        "configs/tiny-cmdap.json": TINY,
        "traffic/tiny-agentmix.json": TINY_AGENTMIX,
        "workloads/tiny-agentmix.json": {
            "job": "serve_agentmix", "flags": FLAGS, "optimizer": "sgd",
            "attention_impl": "xla", "train_batch": 1,
            "train_sequence_length": 16, "trace_seconds": 1,
            "serve": {"slots": 4, "max_seq_len": 48, "prefill_chunk": 8,
                      "kv_layout": "paged", "kv_block_size": 4,
                      "kv_num_blocks": 96, "kv_window_blocks": 96,
                      "prefix_cache": True}},
    }
    for rel, body in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(body))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["workloads"] = [
        {"name": "tiny-agentmix", "config": "tiny-cmdap",
         "traffic": "tiny-agentmix", "chips": 1, "why": "test"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (["tiny-agentmix"] if CELL in m["workloads"]
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
    assert (body["n_embd"], body["n_head"]) == (4096, 128)
    assert (body["experts_held"], body["experts_routed"]) == ([0, 16], 128)
    for key in ("average", "router", "window", "intermediate_size",
                "prefix_dense", "rope", "norms", "head", "initializer_range",
                "embedding_initializer_range", "embedding_initializer_mean"):
        assert key in body["assumed"], key
    assert len(body["departures"]) >= 3 and "8 chips" in body["deployment"]
    # the layers held: one whole period
    assert body["layer_types"][:body["num_hidden_layers"]] == (
        ["sliding_attention"] * 3 + ["full_attention"])
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = run.manifest_entry(manifest, "configs", CONFIG)
    assert entry["source"] == body["source"]
    assert entry["reduced"] == body["reduced"]
    cell = run.manifest_entry(manifest, "workloads", CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve-agentmix"
    assert len(cell["why"]) == 199
    reports = {m["name"] for m in run.metrics_of(manifest, "per_layer", CELL)}
    assert {"full_decode_ms.serve", "swa_decode_ms.serve",
            "full_decode_roofline_pct.serve",
            "swa_decode_roofline_pct.serve", "kv_bytes_a_token.serve",
            "shared_expert_ms.serve", "shared_expert_roofline_pct.serve",
            "attn_proj_ms.serve", "moe_ms.serve", "prefix_hit_pct.serve",
            "chunk_step_ms.serve", "paged_chunk_ms.serve", "engine_iter_ms",
            "device_idle_pct.serve", "device_step_ms.decode.serve",
            "step_join_pct.serve"} <= reports
    assert not {"mla_attend_ms.serve", "gqa_decode_ms.serve",
                "gsa_attend_ms.serve", "paged_decode_ms.serve",
                "prefill_share_pct"} & reports
    assert [m["name"] for m in run.metrics_of(
        manifest, "end_to_end", CELL)] == ["serve_tok_s", "setup_s"]
    # the new readers are this cell's alone
    for name in ("shared_expert_ms.serve", "shared_expert_roofline_pct.serve",
                 "attn_proj_ms.serve"):
        assert run.manifest_entry(manifest, "per_layer",
                                  name)["workloads"] == [CELL]


def test_the_parameter_table_is_the_programs_weight_shapes():
    """The configuration file's arithmetic against the shapes the ops
    declare for the published keys (no array is made)."""
    from flexflow_tpu.ops.attention import AttentionFrontEnd
    from flexflow_tpu.ops.moe import MoEMLPParams, _moe_mlp_weights

    body = harness.load_json("configs", CONFIG + ".json")
    c = command_a_plus_lm_config(body, sequence_length=128)
    assert c.layer_pattern == ("swa", "swa", "swa", "mha")
    d = c.hidden_size
    front = AttentionFrontEnd(d, c.num_heads, False,
                              num_kv_heads=c.num_kv_heads,
                              head_size=c.head_dim, **c.swa)
    count = {w.name: math.prod(w.shape) for w in front.weight_specs(d, d, d)}
    assert count == {"wq": 4096 * 16384, "wk": 4096 * 1024,
                     "wv": 4096 * 1024, "wo": 16384 * 4096}
    assert round(sum(count.values()) / 1e6, 2) == 142.61
    moe = {w.name: math.prod(w.shape) for w in _moe_mlp_weights(
        MoEMLPParams(c.num_experts, c.num_experts_per_tok,
                     c.moe_intermediate_size, **c.moe_routing),
        [(32, 1, d)]) if w.trainable}
    assert "router_bias" not in moe and moe["router"] == 4096 * 128
    assert moe["gate"] + moe["up"] + moe["down"] == 16 * 3 * 4096 * 4096
    shared = moe["shared_gate"] + moe["shared_up"] + moe["shared_down"]
    assert round(shared / 1e6, 2) == 201.33
    layer = sum(count.values()) + sum(moe.values()) + d
    assert round(layer / 1e6, 2) == 1149.77
    whole = 4 * layer + c.vocab_size * d + d  # the tied table once
    assert round(whole / 1e6, 1) == 4733.3
    assert "4,733.3 M" in body["parameters"]["all"]
    # uncut, the same equations count the published 218 B
    uncut = (32 * (sum(count.values()) + moe["router"] + shared + d
                   + 128 * 3 * 4096 * 4096) + 262144 * d + d)
    assert round(uncut / 1e9, 1) == 218.3
    assert front.cache_row_widths(33536) == {"pool_k": 1024, "pool_v": 1024}
    assert "16,384 B" in body["parameters"]["cache_a_token"]


def test_the_aliases_make_the_accepted_readers_floors_this_models():
    """benchmarks/mimo2_events.py reads MiMo's key names: the configuration
    repeats its published values under them, and the floors those readers
    compute are then 1 x 4,096 B and 3 x 4,096 B a cached row."""
    body = harness.load_json("configs", CONFIG + ".json")
    assert "mimo2_events.py" in body["mimo_key_aliases"]
    assert body["hybrid_layer_pattern"] == [
        int(kind == "sliding_attention") for kind in body["layer_types"][:4]]
    for alias in ("swa_num_key_value_heads", "swa_head_dim", "swa_v_head_dim",
                  "v_head_dim"):
        assert body[alias] == body[alias.replace("swa_", "").replace(
            "v_head", "head")], alias
    assert (mimo2_events.layers_of(body, False),
            mimo2_events.layers_of(body, True)) == (1, 3)
    assert mimo2_events.bytes_a_row(body, False, 2) == 4096
    assert mimo2_events.bytes_a_row(body, True, 2) == 3 * 4096
    assert cmdap_events.shared_expert_bytes(body, 2) == 4 * 201_326_592 * 2


def test_the_mix_and_the_cell_are_the_issues():
    mix = harness.load_json("traffic", "serve-agentmix.json")
    cell = harness.load_json("workloads", CELL + ".json")
    assert mix["kind"] == "closed_loop_sessions"
    assert set(mix) >= set(harness.load_json("traffic",
                                             "serve-sessions.json"))
    window = harness.load_json("configs", CONFIG + ".json")["sliding_window"]
    histories = traffic.quantiles(mix["history_tokens"], mix["clients"])
    assert len(histories) == 32 == mix["cycle"] == cell["serve"]["slots"]
    assert (histories[0], histories[-1], sum(histories)) == (
        2139, 31379, 354445)
    # 8 sessions are shorter than the window, and three of them cross it
    # inside a request (history + turn + reply past 4,096)
    under = [h for h in histories if h < window]
    assert under == [2139, 2332, 2543, 2774, 3025, 3298, 3597, 3922]
    turns, replies = traffic.request_sizes(mix)
    assert (min(turns), max(turns)) == (66, 496)
    assert 128 <= min(replies) and max(replies) <= 512
    crossing = [h for h in under if h + max(turns) + max(replies) > window]
    assert crossing == [3298, 3597, 3922]
    # the two compared streams: one crossing the window in every request,
    # one far past it; the check's prompt past it by two chunks
    near = [histories[c] for c in mix["check_stream_histories"]]
    assert near == [3922, 11094]
    assert near[0] < window < near[0] + min(turns) + min(replies)
    assert mix["check_history_tokens"] == [4700]
    assert 4700 - window > 2 * cell["serve"]["prefill_chunk"]
    assert max(replies) <= command_a_plus_reference.ROWS
    serve = cell["serve"]
    assert (max(histories) + max(turns) + max(replies)
            <= serve["max_seq_len"])
    assert serve["max_seq_len"] == 33536 and serve["prefill_chunk"] == 256
    assert serve["prefix_cache"] is True
    # the global pool holds every history and what 32 live requests draw;
    # the window pool what the histories pin (the blocks of their last
    # 4,096 rows) beside what 32 slots write, and its reservations
    bs = serve["kv_block_size"]
    assert bs in (128, 256) and serve["max_seq_len"] % bs == 0
    live = -(-(max(turns) + max(replies)) // bs) + 2
    need = sum(-(-h // bs) for h in histories) + 32 * live
    assert need < serve["kv_num_blocks"]
    assert 1.7e9 < serve["kv_num_blocks"] * bs * 4096 < 2.1e9
    pinned = sum((h - 1) // bs - max(h - window, 0) // bs + 1
                 for h in histories)
    slot_blocks = -(-(window - 1 + 256) // bs) + 2
    assert 32 * slot_blocks < serve["kv_window_blocks"]
    assert pinned + 32 * live < serve["kv_window_blocks"]
    assert serve["kv_window_blocks"] * bs * 3 * 4096 < 2.6e9
    assert cell["kv_block_size_why"] and cell["kv_window_blocks_why"]
    assert cell["job"] == "serve_agentmix"
    assert "--dtype" in cell["flags"] and "bf16" in cell["flags"]


def test_the_benchmarks_reference_is_the_programs():
    mine = open(command_a_plus_reference.__file__).read()
    theirs = open(program_reference.__file__).read()
    body = theirs[theirs.index("float32, `jax.default_matmul"):]
    body = body.replace("from . import deepseek_v32_reference as dsa",
                        "from benchmarks import deepseek_v32_reference "
                        "as dsa")
    assert "The benchmark's own copy" in mine and body in mine
    assert 0 < command_a_plus_reference.LOGIT_TOL < 0.1
    assert 0 < command_a_plus_reference.CACHE_TOL < 0.5
    assert 0 < command_a_plus_reference.ROUTE_MARGIN < 0.2
    assert command_a_plus_reference.SPOILS == program_reference.SPOILS
    assert {"sequential_block", "rope_on_global", "rope_half", "window_off",
            "shared_summed", "rmsnorm", "softmax_scores",
            "e4m3"} == set(program_reference.SPOILS[1:])
    assert command_a_plus_reference.last_global_layer(
        harness.load_json("configs", CONFIG + ".json")) == 3


def test_agentmix_job_runs_a_window_through_serve(tiny, capsys):
    assert run.main(["--workload", "tiny-agentmix", "--seed",
                     str(2**31 + 11), "--seconds", "1.5", "--trace", "0"],
                    tiny) == 0
    out = capsys.readouterr().out
    line = result_line(out)
    assert line["correct"] is True and line["failed"] == 0, out
    assert "prompt tokens: sound" in out
    assert "0 without their whole history" in out
    assert "0 histories moved or evicted" in out and "0 dropped" in out
    assert out.count("replayed with 4 slots live") == 2
    assert out.count("rows of a served turn") == 2
    assert "window blocks given back" in out
    assert line["attempted"] >= 4
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("control", ["sequential_block", "rope_half",
                                     "window_off", "shared_summed",
                                     "rmsnorm", "lost_window_block"])
def test_agentmix_job_is_not_correct_under_a_control(tiny, capsys,
                                                     monkeypatch, control):
    """The builder's controls through the job's own hook: spoils of the
    reference (every one moves the reference's logits:
    tests/test_command_a_plus.py), and the replay with the window blocks of
    every history zeroed."""
    load = harness.load_module

    def loaded(*parts):
        module = load(*parts)
        if parts == ("jobs", "serve_agentmix.py"):
            job = module.run
            module.run = lambda ctx: job(ctx, control=control)
        return module

    monkeypatch.setattr(harness, "load_module", loaded)
    argv = ["--workload", "tiny-agentmix", "--seed", "5", "--seconds", "0.3",
            "--trace", "0"]
    if control != "lost_window_block":
        assert run.main(argv, tiny) == 0
        assert result_line(capsys.readouterr().out)["correct"] is False
        return
    # as test_mimo2_cell.py: on the CPU a chunk step is a rectangle and the
    # replay does not feed a stream's first token, so a turn prefilled over
    # zeroed window blocks may sample another first token than the loop
    # served, which the replay refuses outright
    try:
        assert run.main(argv, tiny) == 0
    except RuntimeError as e:
        assert "not fed the served stream" in str(e)
    else:
        assert result_line(capsys.readouterr().out)["correct"] is False


def test_traced_agentmix_run_reads_what_it_can(tiny, capsys, monkeypatch):
    """--trace 1 with the trace steered to the recorded GPT-2 one (the CPU
    has no device plane): the job compiles the decode step's text for the
    scoped instructions of the three readers' modules; the readers find no
    step in that trace and leave the device metrics out; the counters'
    metrics are there."""
    import jax

    with open(os.path.join(HERE, "recorded_trace.textproto")) as f:
        recorded = trace.read(
            jax.profiler.ProfileData.from_text_proto(f.read()))
    monkeypatch.setattr(trace, "read_file", lambda path: recorded)
    seen = {}
    scoped = cmdap_events.scoped_instructions
    monkeypatch.setattr(
        cmdap_events, "scoped_instructions",
        lambda text: seen.setdefault("pairs", scoped(text)))
    assert run.main(["--workload", "tiny-agentmix", "--seed", "1",
                     "--seconds", "30", "--trace", "1"], tiny) == 0
    out = capsys.readouterr().out
    line = result_line(out)
    assert line["correct"] is True, out
    assert {"prefix_hit_pct.serve", "engine_iter_ms", "chunk_step_ms.serve",
            "kv_bytes_a_token.serve", "ffcompile_s",
            "xla_compile_s"} <= set(line["metrics"])
    assert not {"full_decode_ms.serve", "swa_decode_ms.serve",
                "shared_expert_ms.serve", "shared_expert_roofline_pct.serve",
                "attn_proj_ms.serve"} & set(line["metrics"])
    assert line["metrics"]["prefix_hit_pct.serve"]["value"] > 50
    # a held token costs the global layer's row and the windows' share,
    # not every layer's rows: 4 layers x 2 x 32 x 4 B = 1,024 B
    assert 256 < line["metrics"]["kv_bytes_a_token.serve"]["value"] < 1024
    assert {"gqa.qkv", "gqa.out", "swa.qkv", "swa.out",
            "moe.shared"} <= {s for _, s in seen["pairs"]}


HLO = '''
  %fusion.1 = bf16[32,16384]{1,0} fusion(%p), kind=kOutput, calls=%f.1, metadata={op_name="jit(decode_step)/l0_attn/swa.qkv/dot_general"}
  %custom-call.2 = bf16[8,4]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_step)/l0_attn/swa.attend/flash_attention_paged_decode_window_grouped"}
  %fusion.3 = bf16[32,4096]{1,0} fusion(%z), kind=kOutput, calls=%f.3, metadata={op_name="jit(decode_step)/l3_attn/gqa.out/dot_general"}
  %fusion.4 = bf16[32,16384]{1,0} fusion(%z), kind=kOutput, calls=%f.4, metadata={op_name="jit(decode_step)/l3_moe/moe.shared/dot_general"}
  ROOT %fusion.5 = bf16[8,4]{1,0} fusion(%z), kind=kOutput, calls=%f.5, metadata={op_name="jit(decode_step)/lm_head/dot_general"}
'''


def hand_made_run(pairs, steps, itemsize=2):
    ms = 1_000_000
    ops = [("%fusion.1 = bf16[32,16384] fusion(%p)", 0, 1 * ms),
           ("%custom-call.2 = bf16[8,4] custom-call(%p)", 1 * ms, 4 * ms),
           ("%fusion.3 = bf16[32,4096] fusion(%z)", 4 * ms, 5 * ms),
           ("%fusion.4 = bf16[32,16384] fusion(%z)", 5 * ms, 9 * ms),
           ("%fusion.5 = bf16[8,4] fusion(%z)", 9 * ms, 10 * ms),
           # the second step
           ("%fusion.1 = bf16[32,16384] fusion(%p)", 20 * ms, 21 * ms),
           ("%fusion.4 = bf16[32,16384] fusion(%z)", 21 * ms, 25 * ms),
           # a chunk step's events: another step's interval, left out
           ("%fusion.4 = bf16[32,16384] fusion(%z)", 40 * ms, 49 * ms)]
    r = types.SimpleNamespace(
        result={"counters": {"cmdap_instructions": pairs,
                             "weight_itemsize": itemsize}},
        config=harness.load_json("configs", CONFIG + ".json"),
        peaks={"hbm_bytes_per_s": 8.0e11})
    r.trace = trace.Trace([trace.Chip(0, ops, [])], [], (0, 60 * ms))
    r.device_steps = device_steps.Record(
        steps, len(steps), {}, 0.0, (0.0, 0.0), [], [])
    return r


def a_step(i, kind, start, end, **args):
    ms = 1_000_000
    return device_steps.Step(
        id=i, kind=kind, bucket=0, chunk_start=0, rows=32, start=start * ms,
        end=end * ms, busy_ns=0.0, idle_before_ns=0.0, args=args)


NAMES = ("shared_expert_ms.serve", "shared_expert_roofline_pct.serve",
         "attn_proj_ms.serve")
STEPS = [a_step(1, "decode", 0, 10), a_step(2, "decode", 20, 30),
         a_step(3, "chunk", 40, 50)]


def test_the_new_readers_on_hand_made_events():
    """Two pure-decode steps and a chunk step: the readers take the events
    inside the device's own intervals of the decode steps, by scope, and
    hold the shared experts to their weights' bytes once a step."""
    pairs = cmdap_events.scoped_instructions(HLO)
    assert pairs == [["fusion.1", "swa.qkv"], ["fusion.3", "gqa.out"],
                     ["fusion.4", "moe.shared"]]
    r = hand_made_run(pairs, STEPS)
    read = lambda name: harness.load_reader(name).read(r)  # noqa: E731
    assert read("attn_proj_ms.serve") == pytest.approx(1.5)
    assert read("shared_expert_ms.serve") == pytest.approx(4.0)
    # by hand: 4 layers x 201,326,592 numbers x 2 B = 1,610,612,736 B at
    # 8e11 B/s = 2.013 ms a step, over 4 ms of moe.shared
    assert read("shared_expert_roofline_pct.serve") == pytest.approx(
        100 * 1_610_612_736 / 8.0e11 / 4e-3)
    assert read("shared_expert_roofline_pct.serve") < 100


def test_the_new_readers_find_nothing_on_a_parent_or_a_bad_join():
    pairs = cmdap_events.scoped_instructions(HLO)
    no_pairs = hand_made_run(None, STEPS)
    unjoined = hand_made_run(pairs, STEPS)
    unjoined.device_steps.dispatched = 4    # one step was not joined
    nothing = hand_made_run(pairs, STEPS)
    nothing.device_steps = None             # a program without `step` ids
    for r in (no_pairs, unjoined, nothing):
        for name in NAMES:
            assert harness.load_reader(name).read(r) is None, name
    # a run that left no item size reads the times and no share
    no_size = hand_made_run(pairs, STEPS, itemsize=None)
    assert harness.load_reader(NAMES[0]).read(no_size) == pytest.approx(4.0)
    assert harness.load_reader(NAMES[1]).read(no_size) is None
