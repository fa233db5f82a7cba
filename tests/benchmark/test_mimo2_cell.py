"""The configuration, job, traffic, reference and readers of
`mimo2f-serve-longdoc` (PR 41) on the CPU at tiny widths, as
test_keye2_cell.py does it for PR 38's: the real sizes run only on the
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
        "benchmarks_run_mimo2", os.path.join(REPO, "benchmarks", "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load_run()
from benchmarks import (  # noqa: E402
    device_steps, harness, mimo2_events, mimo_v2_flash_reference, trace,
    traffic,
)
from flexflow_tpu.models import (  # noqa: E402
    mimo_v2_flash_lm_config, mimo_v2_flash_reference as program_reference,
)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(CATALOG if os.path.exists(CATALOG) else os.devnull) as _f:
    _rows = [json.loads(line) for line in _f if '"MiMo-V2-Flash"' in line]
PATTERN = [0, 1, 1, 1, 1] + [0, 1, 1, 1, 1, 1] * 7 + [0]
# the catalog row's config, key for key (kept here: the catalog is not
# part of the repository)
PUBLISHED = {
    "attention_value_scale": 0.707, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 16384,
    "max_position_embeddings": 262144, "model_type": "mimo_v2_flash",
    "num_attention_heads": 64, "head_dim": 192, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "layernorm_epsilon": 1e-05,
    "rope_theta": 5000000, "tie_word_embeddings": False,
    "vocab_size": 152576, "partial_rotary_factor": 0.334,
    "sliding_window": 128, "swa_rope_theta": 10000, "attention_bias": False,
    "v_head_dim": 128, "hybrid_layer_pattern": PATTERN,
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "sliding_window_size": 128,
    "attention_chunk_size": 128, "moe_layer_freq": [0] + [1] * 47,
    "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": None, "num_experts_per_tok": 8,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc",
    "routed_scaling_factor": None, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 8, "swa_head_dim": 192,
    "swa_v_head_dim": 128}
REDUCED = {"num_hidden_layers": 7, "n_routed_experts": 16,
           "vocab_size": 19072}
# hidden 64; 4 query heads of 24 / 16 over 1 (global) and 2 (window) KV
# heads, RoPE on 8 lanes, a window of 6; layers [global dense, window,
# window, global]; 4 of 16 experts of 24 held, 4 a token
TINY = {
    **PUBLISHED, "source": "the test file", "hidden_size": 64,
    "num_attention_heads": 4, "swa_num_attention_heads": 4,
    "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
    "head_dim": 24, "swa_head_dim": 24, "v_head_dim": 16,
    "swa_v_head_dim": 16, "n_embd": 64, "n_head": 4,
    "intermediate_size": 96, "moe_intermediate_size": 24,
    "sliding_window": 6, "sliding_window_size": 6, "attention_chunk_size": 6,
    "hybrid_layer_pattern": [0, 1, 1, 0], "moe_layer_freq": [0, 1, 1, 1],
    "num_hidden_layers": 4, "vocab_size": 97, "n_routed_experts": 4,
    "experts_held": [0, 4], "experts_routed": 16, "num_experts_per_tok": 4,
    "initializer_range": 0.1, "embedding_initializer_range": 0.5,
    "sink_initializer_range": 2.0,
    "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
    "reduced_from": {"num_hidden_layers": 48, "n_routed_experts": 256,
                     "vocab_size": 152576}}
TINY_LONGDOC = {
    "kind": "closed_loop_sessions", "clients": 4, "cycle": 4,
    "history_tokens": {"dist": "log_uniform", "min": 10, "max": 30},
    "prompt_tokens": {"dist": "log_uniform", "min": 3, "max": 8},
    "new_tokens": {"dist": "uniform", "min": 2, "max": 5},
    "check_history_tokens": [11, 21], "check_stream_histories": [0, 3]}
FLAGS = ["--mesh", "1,1,1,1", "--no-verify-plan"]
CELL = "mimo2f-serve-longdoc"
CONFIG = "mimo-v2-flash"


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    files = {
        "configs/tiny-mimo2.json": TINY,
        "traffic/tiny-longdoc.json": TINY_LONGDOC,
        "workloads/tiny-longdoc.json": {
            "job": "serve_longdoc", "flags": FLAGS, "optimizer": "sgd",
            "attention_impl": "xla", "train_batch": 1,
            "train_sequence_length": 16, "trace_seconds": 1,
            "serve": {"slots": 4, "max_seq_len": 48, "prefill_chunk": 8,
                      "kv_layout": "paged", "kv_block_size": 4,
                      "kv_num_blocks": 96, "kv_window_blocks": 40,
                      "prefix_cache": True}},
    }
    for rel, body in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(body))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["workloads"] = [
        {"name": "tiny-longdoc", "config": "tiny-mimo2",
         "traffic": "tiny-longdoc", "chips": 1, "why": "test"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (["tiny-longdoc"] if CELL in m["workloads"]
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
    assert (body["n_embd"], body["n_head"]) == (4096, 64)
    assert (body["experts_held"], body["experts_routed"]) == ([0, 16], 256)
    for key in ("sink", "attention_value_scale", "window", "repeated_keys",
                "rope", "router", "initializer_range",
                "embedding_initializer_range", "sink_initializer_range"):
        assert key in body["assumed"], key
    assert len(body["departures"]) >= 3 and "16 chips" in body["deployment"]
    # the layers held: the leading dense global layer and one rotation of
    # the published period of six
    held = body["hybrid_layer_pattern"][:body["num_hidden_layers"]]
    assert held == [0, 1, 1, 1, 1, 0, 1]
    assert sorted(held[1:]) == sorted(PATTERN[5:11])
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = run.manifest_entry(manifest, "configs", CONFIG)
    assert entry["source"] == body["source"]
    assert entry["reduced"] == body["reduced"]
    cell = run.manifest_entry(manifest, "workloads", CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve-longdoc"
    reports = {m["name"] for m in run.metrics_of(manifest, "per_layer", CELL)}
    assert {"full_decode_ms.serve", "swa_decode_ms.serve",
            "full_decode_roofline_pct.serve",
            "swa_decode_roofline_pct.serve", "kv_bytes_a_token.serve",
            "moe_ms.serve", "prefix_hit_pct.serve", "chunk_step_ms.serve",
            "engine_iter_ms", "device_idle_pct.serve",
            "device_step_ms.decode.serve", "step_join_pct.serve"} <= reports
    assert not {"mla_attend_ms.serve", "gqa_decode_ms.serve",
                "gsa_attend_ms.serve", "paged_decode_ms.serve",
                "prefill_share_pct"} & reports
    assert [m["name"] for m in run.metrics_of(
        manifest, "end_to_end", CELL)] == ["serve_tok_s", "setup_s"]


def test_the_parameter_table_is_the_programs_weight_shapes():
    """The configuration file's arithmetic against the shapes the ops
    declare for the published keys (no array is made)."""
    from flexflow_tpu.ops.attention import AttentionFrontEnd
    from flexflow_tpu.ops.moe import MoEMLPParams, _moe_mlp_weights

    body = harness.load_json("configs", CONFIG + ".json")
    c = mimo_v2_flash_lm_config(body, sequence_length=128)
    assert c.layer_pattern == ("mha", "swa", "swa", "swa", "swa", "mha",
                               "swa")
    assert (c.first_k_dense, c.rope_dim, c.value_scale) == (1, 64, 0.707)
    d = c.hidden_size

    def front(**kind):
        return AttentionFrontEnd(
            d, c.num_heads, False, head_size=c.head_dim,
            v_head_size=c.v_head_dim, rope_dim=c.rope_dim,
            value_scale=c.value_scale,
            **{"rope_theta": c.rope_theta, "num_kv_heads": c.num_kv_heads,
               **kind})

    def count(f):
        return {w.name: math.prod(w.shape) for w in f.weight_specs(d, d, d)}

    full, swa = front(), front(**c.swa)
    assert (swa.window, swa.sink, swa.kv_heads, swa.rope_theta) == (
        128, True, 8, 1e4)
    assert (full.window, full.sink, full.kv_heads, full.rope_theta) == (
        0, False, 4, 5e6)
    assert round(sum(count(full).values()) / 1e6, 2) == 89.13
    assert round(sum(count(swa).values()) / 1e6, 2) == 94.37
    assert count(swa)["sink"] == 64 and count(full)["wo"] == 8192 * 4096
    moe = {w.name: math.prod(w.shape) for w in _moe_mlp_weights(
        MoEMLPParams(c.num_experts, c.num_experts_per_tok,
                     c.moe_intermediate_size, **c.moe_routing),
        [(32, 1, d)]) if w.trainable}
    assert moe["router"] == 4096 * 256
    assert moe["gate"] + moe["up"] + moe["down"] == 16 * 3 * 4096 * 2048
    dense = 3 * d * c.intermediate_size
    whole = (2 * sum(count(full).values()) + 5 * sum(count(swa).values())
             + dense + 6 * sum(moe.values()) + 14 * d + d
             + 2 * c.vocab_size * d)
    assert round(whole / 1e6) == 3430
    assert "3,430 M" in body["parameters"]["all"]
    # the cache: rows of whole 128-lane tiles, though heads of 192 are not
    assert full.cache_row_widths(33536) == {"pool_k": 768, "pool_v": 512}
    assert swa.cache_row_widths(33536) == {"pool_k": 1536, "pool_v": 1024}
    assert mimo2_events.bytes_a_row(body, False, 2) == 2 * 2560
    assert mimo2_events.bytes_a_row(body, True, 2) == 5 * 5120
    assert "30,720 B" in body["parameters"]["cache_a_token"]


def test_the_mix_and_the_cell_are_the_issues():
    mix = harness.load_json("traffic", "serve-longdoc.json")
    cell = harness.load_json("workloads", CELL + ".json")
    assert mix["kind"] == "closed_loop_sessions"
    assert set(mix) >= set(harness.load_json("traffic",
                                             "serve-sessions.json"))
    histories = traffic.quantiles(mix["history_tokens"], mix["clients"])
    assert len(histories) == 32 == mix["cycle"] == cell["serve"]["slots"]
    assert 8192 <= min(histories) and max(histories) <= 32768
    assert 560_000 < sum(histories) < 575_000
    assert histories == sorted(histories) and len(set(histories)) == 32
    questions, replies = traffic.request_sizes(mix)
    assert 32 <= min(questions) and max(questions) <= 128
    assert 128 <= min(replies) and max(replies) <= 512
    # the two compared streams' contexts: near 8.4 k and 16 k
    near = [histories[c] for c in mix["check_stream_histories"]]
    assert 8_000 < near[0] < 9_000 and 15_500 < near[1] < 16_500
    assert max(replies) <= mimo_v2_flash_reference.ROWS
    serve = cell["serve"]
    assert (max(histories) + max(questions) + max(replies)
            <= serve["max_seq_len"])
    assert serve["max_seq_len"] == 33536 and serve["prefill_chunk"] == 256
    assert serve["prefix_cache"] is True
    # the global pool holds every history and what 32 live requests draw;
    # the window pool what 32 slots may hold at once and the histories'
    # last rows, under 2 GB
    bs = serve["kv_block_size"]
    need = sum(-(-h // bs) for h in histories) + 32 * 7
    assert need < serve["kv_num_blocks"] == 5400
    assert 3.4e9 < serve["kv_num_blocks"] * bs * 5120 < 3.8e9
    slot_blocks = -(-(128 - 1 + 256) // bs) + 2
    assert 32 * slot_blocks + 32 * 2 < serve["kv_window_blocks"] == 512
    assert serve["kv_window_blocks"] * bs * 25600 < 2e9
    assert cell["kv_block_size_why"] and cell["kv_window_blocks_why"]
    assert cell["job"] == "serve_longdoc"
    assert "--dtype" in cell["flags"] and "bf16" in cell["flags"]


def test_the_benchmarks_reference_is_the_programs():
    mine = open(mimo_v2_flash_reference.__file__).read()
    theirs = open(program_reference.__file__).read()
    head = "builds it\nfrom `mimo_v2_flash_lm_config`: the forward"
    body = theirs[theirs.index("float32, `jax.default_matmul"):]
    body = body.replace("from . import deepseek_v32_reference as dsa",
                        "from benchmarks import deepseek_v32_reference "
                        "as dsa")
    assert head in mine and body in mine
    assert 0 < mimo_v2_flash_reference.LOGIT_TOL < 0.1
    assert 0 < mimo_v2_flash_reference.CACHE_TOL < 0.5
    assert 0 < mimo_v2_flash_reference.ROUTE_MARGIN < 0.2
    assert mimo_v2_flash_reference.SPOILS == program_reference.SPOILS
    assert {"window_off", "sink_off", "value_scale_off", "thetas_swapped",
            "rope_whole"} <= set(program_reference.SPOILS)


def test_longdoc_job_runs_a_window_through_serve(tiny, capsys):
    assert run.main(["--workload", "tiny-longdoc", "--seed",
                     str(2**31 + 11), "--seconds", "1.5", "--trace", "0"],
                    tiny) == 0
    out = capsys.readouterr().out
    line = result_line(out)
    assert line["correct"] is True and line["failed"] == 0, out
    assert "prompt tokens: sound" in out
    assert "0 without their whole history" in out
    assert "0 histories moved or evicted" in out and "0 dropped" in out
    assert out.count("replayed with 4 slots live") == 2
    assert out.count("rows of a served question") == 2
    assert line["attempted"] >= 4
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("control", ["window_off", "sink_off",
                                     "value_scale_off", "thetas_swapped",
                                     "rope_whole", "lost_window_block"])
def test_longdoc_job_is_not_correct_under_a_control(tiny, capsys,
                                                    monkeypatch, control):
    """The builder's controls through the job's own hook: each spoil of
    the reference, and the replay with the window block at the end of
    every history zeroed."""
    load = harness.load_module

    def loaded(*parts):
        module = load(*parts)
        if parts == ("jobs", "serve_longdoc.py"):
            job = module.run
            module.run = lambda ctx: job(ctx, control=control)
        return module

    monkeypatch.setattr(harness, "load_module", loaded)
    argv = ["--workload", "tiny-longdoc", "--seed", "5", "--seconds", "0.3",
            "--trace", "0"]
    if control != "lost_window_block":
        assert run.main(argv, tiny) == 0
        assert result_line(capsys.readouterr().out)["correct"] is False
        return
    # the CPU lays a chunk step out as a rectangle, where the replay does
    # not feed a stream's first token (on the chip a chunk rides as rows
    # and it does): a question prefilled over a zeroed window samples
    # another first token than the loop served, which the replay refuses
    # outright; where it happens to sample the same, the question's rows
    # in the last global layer give the lost block away
    try:
        assert run.main(argv, tiny) == 0
    except RuntimeError as e:
        assert "not fed the served stream" in str(e)
    else:
        assert result_line(capsys.readouterr().out)["correct"] is False


def test_traced_longdoc_run_reads_what_it_can(tiny, capsys, monkeypatch):
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
    scoped = mimo2_events.scoped_instructions
    monkeypatch.setattr(
        mimo2_events, "scoped_instructions",
        lambda text: seen.setdefault("pairs", scoped(text)))
    assert run.main(["--workload", "tiny-longdoc", "--seed", "1",
                     "--seconds", "30", "--trace", "1"], tiny) == 0
    out = capsys.readouterr().out
    line = result_line(out)
    assert line["correct"] is True, out
    assert {"prefix_hit_pct.serve", "engine_iter_ms", "chunk_step_ms.serve",
            "kv_bytes_a_token.serve", "ffcompile_s",
            "xla_compile_s"} <= set(line["metrics"])
    assert not {"full_decode_ms.serve", "swa_decode_ms.serve",
                "full_decode_roofline_pct.serve",
                "swa_decode_roofline_pct.serve"} & set(line["metrics"])
    assert line["metrics"]["prefix_hit_pct.serve"]["value"] > 50
    # a held token costs the global layers' rows and the windows' share,
    # not every layer's rows: 2 x (24 + 16) x 4 B global, 2 x 2 x 40 x 4 B
    # a window layer's row
    assert 320 < line["metrics"]["kv_bytes_a_token.serve"]["value"] < 960
    assert {"swa.attend", "gqa.attend", "moe.route", "moe.experts",
            "moe.combine"} <= {s for _, s in seen["pairs"]}


HLO = '''
  %custom-call.1 = bf16[8,4]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_step)/l0_attn/gqa.attend/flash_attention_paged_decode_grouped"}
  %custom-call.2 = bf16[8,4]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_step)/l1_attn/swa.attend/flash_attention_paged_decode_window_grouped"}
  %fusion.3 = bf16[8,4]{1,0} fusion(%z), kind=kOutput, calls=%f.3, metadata={op_name="jit(decode_step)/l1_moe/moe.combine/mul"}
  ROOT %fusion.4 = bf16[8,4]{1,0} fusion(%z), kind=kOutput, calls=%f.4, metadata={op_name="jit(decode_step)/lm_head/dot_general"}
'''


def hand_made_run(pairs, steps):
    ms = 1_000_000
    ops = [("%custom-call.1 = bf16[8,4] custom-call(%p)", 0, 4 * ms),
           ("%custom-call.2 = bf16[8,4] custom-call(%p)", 4 * ms, 5 * ms),
           ("%fusion.3 = bf16[8,4] fusion(%z)", 5 * ms, 7 * ms),
           ("%fusion.4 = bf16[8,4] fusion(%z)", 7 * ms, 10 * ms),
           # the second step: a longer context, the same window
           ("%custom-call.1 = bf16[8,4] custom-call(%p)", 20 * ms, 28 * ms),
           ("%custom-call.2 = bf16[8,4] custom-call(%p)", 28 * ms, 29 * ms),
           # a chunk step's events: another step's interval, left out
           ("%custom-call.1 = bf16[8,4] custom-call(%p)", 40 * ms, 49 * ms)]
    r = types.SimpleNamespace(
        result={"counters": {"mimo2_instructions": pairs}},
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


NAMES = ("full_decode_ms.serve", "swa_decode_ms.serve",
         "full_decode_roofline_pct.serve", "swa_decode_roofline_pct.serve")


def test_the_new_readers_on_hand_made_events():
    """Two pure-decode steps and a chunk step: the readers take the events
    inside the device's own intervals of the decode steps, by scope, and
    hold them to the bytes the steps' own arguments count."""
    pairs = mimo2_events.scoped_instructions(HLO)
    assert pairs == [["custom-call.1", "gqa.attend"],
                     ["custom-call.2", "swa.attend"],
                     ["fusion.3", "moe.combine"]]
    steps = [a_step(1, "decode", 0, 10, kv_itemsize=2, kv_rows=32 * 10_000,
                    window_rows=32 * 128),
             a_step(2, "decode", 20, 30, kv_itemsize=2, kv_rows=32 * 20_000,
                    window_rows=32 * 128),
             a_step(3, "chunk", 40, 50, kv_itemsize=2, kv_rows=32 * 20_000,
                    window_rows=32 * 128)]
    r = hand_made_run(pairs, steps)
    read = lambda name: harness.load_reader(name).read(r)  # noqa: E731
    assert read("full_decode_ms.serve") == pytest.approx(6.0)
    assert read("swa_decode_ms.serve") == pytest.approx(1.0)
    found = mimo2_events.by_scope(r)
    assert found["other"] == pytest.approx(3e-3) and len(found["steps"]) == 2
    # by hand: 960,000 context rows x 2 layers x 2,560 B = 4,915,200,000 B
    # at 8e11 B/s = 6.144 ms, over 12 ms of gqa.attend
    assert read("full_decode_roofline_pct.serve") == pytest.approx(
        100 * 4_915_200_000 / 8.0e11 / 12e-3)
    # 2 steps x 4,096 window rows x 5 layers x 5,120 B = 209,715,200 B =
    # 262 us, over 2 ms of swa.attend
    assert read("swa_decode_roofline_pct.serve") == pytest.approx(
        100 * 209_715_200 / 8.0e11 / 2e-3)
    assert all(read(name) < 100 for name in NAMES[2:])


def test_the_new_readers_find_nothing_on_a_parent_or_a_bad_join():
    pairs = mimo2_events.scoped_instructions(HLO)
    # a parent's span has `kv_rows` and no `window_rows`
    steps = [a_step(1, "decode", 0, 10, kv_itemsize=2, kv_rows=1000)]
    no_window = hand_made_run(pairs, steps)
    assert harness.load_reader(NAMES[1]).read(no_window) == pytest.approx(1.0)
    assert harness.load_reader(NAMES[3]).read(no_window) is None
    no_pairs = hand_made_run(None, steps)
    unjoined = hand_made_run(pairs, steps)
    unjoined.device_steps.dispatched = 2    # one step was not joined
    nothing = hand_made_run(pairs, steps)
    nothing.device_steps = None             # a program without `step` ids
    for r in (no_pairs, unjoined, nothing):
        for name in NAMES:
            assert harness.load_reader(name).read(r) is None, name
    assert harness.load_reader("kv_bytes_a_token.serve").read(
        no_pairs) is None
