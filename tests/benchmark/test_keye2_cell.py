"""The configuration, job, traffic, reference and readers of
`keye2-serve-mediaqa` (PR 38) on the CPU at tiny widths, as
test_dsv32_cell.py does it for PR 31's: the real sizes run only on the
chip.
"""

import importlib.util
import json
import math
import os
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def _load_run():
    spec = importlib.util.spec_from_file_location(
        "benchmarks_run_keye2", os.path.join(REPO, "benchmarks", "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load_run()
from benchmarks import (  # noqa: E402
    device_steps, harness, keye2_events, keye_vl2_reference, trace, traffic,
)
from flexflow_tpu.models import (  # noqa: E402
    keye_vl2_lm_config, keye_vl2_reference as program_reference,
)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(CATALOG if os.path.exists(CATALOG) else os.devnull) as _f:
    _rows = [json.loads(line) for line in _f
             if '"Keye-VL-2.0-30B-A3B"' in line]
# the catalog row's config, key for key (kept here: the catalog is not
# part of the repository)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 6}
TINY = {
    **PUBLISHED, "source": "the test file", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_embd": 64, "n_head": 4, "moe_intermediate_size": 24,
    "num_experts": 8, "num_local_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "vocab_size": 97,
    "sa_config": {**PUBLISHED["sa_config"], "indexer_head_dim": 8,
                  "indexer_num_heads": 2, "topk": 8},
    "initializer_range": 0.1, "embedding_initializer_range": 0.5,
    "reduced": ["num_hidden_layers"],
    "reduced_from": {"num_hidden_layers": 48}}
TINY_MEDIAQA = {
    "kind": "closed_loop_sessions", "clients": 4, "cycle": 4,
    "history_tokens": {"dist": "log_uniform", "min": 10, "max": 30},
    "prompt_tokens": {"dist": "log_uniform", "min": 3, "max": 8},
    "new_tokens": {"dist": "uniform", "min": 2, "max": 5},
    "check_history_tokens": [11, 14], "check_stream_histories": [0, 3]}
FLAGS = ["--mesh", "1,1,1,1", "--no-verify-plan"]
CELL = "keye2-serve-mediaqa"


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    files = {
        "configs/tiny-keye2.json": TINY,
        "traffic/tiny-mediaqa.json": TINY_MEDIAQA,
        "workloads/tiny-mediaqa.json": {
            "job": "serve_mediaqa", "flags": FLAGS, "optimizer": "sgd",
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
        {"name": "tiny-mediaqa", "config": "tiny-keye2",
         "traffic": "tiny-mediaqa", "chips": 1, "why": "test"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (["tiny-mediaqa"] if CELL in m["workloads"]
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
    body = harness.load_json("configs", "keye-vl-2.0-30b-a3b.json")
    for key, value in PUBLISHED.items():
        assert body[key] == REDUCED.get(key, value), key
    if _rows:  # the catalog, where it is at hand
        assert _rows[0]["config"] == PUBLISHED
        assert _rows[0]["source_url"] == body["source"]
    assert body["reduced"] == list(REDUCED)
    assert body["reduced_from"] == {k: PUBLISHED[k] for k in REDUCED}
    assert (body["n_embd"], body["n_head"]) == (2048, 32)
    for key in ("qk_norm", "rope", "indexer_equations", "indexer_queries",
                "indexer_key_norm", "indexer_rope", "indexer_score_scale",
                "chunk_sizes", "router", "initializer_range",
                "embedding_initializer_range"):
        assert key in body["assumed"], key
    assert body["embedding_initializer_range"] == 1.0
    assert len(body["departures"]) >= 3 and "eight" in body["deployment"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = run.manifest_entry(manifest, "configs", "keye-vl-2.0-30b-a3b")
    assert entry["source"] == body["source"]
    assert entry["reduced"] == body["reduced"]
    cell = run.manifest_entry(manifest, "workloads", CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve-mediaqa"
    reports = {m["name"] for m in run.metrics_of(manifest, "per_layer", CELL)}
    assert {"gsa_attend_ms.serve", "gsa_attend_roofline_pct.serve",
            "dsa_index_roofline_pct.serve", "dsa_index_ms.serve",
            "moe_ms.serve", "prefix_hit_pct.serve", "chunk_step_ms.serve",
            "engine_iter_ms", "device_idle_pct.serve",
            "device_step_ms.decode.serve", "step_join_pct.serve"} <= reports
    assert not {"mla_attend_ms.serve", "gqa_decode_ms.serve",
                "paged_decode_ms.serve", "prefill_share_pct"} & reports
    assert [m["name"] for m in run.metrics_of(
        manifest, "end_to_end", CELL)] == ["serve_tok_s", "setup_s"]


def test_the_parameter_table_is_the_programs_weight_shapes():
    """The configuration file's arithmetic against the shapes the ops
    declare for the published keys (no array is made)."""
    from flexflow_tpu.ops.attention import AttentionFrontEnd
    from flexflow_tpu.ops.moe import MoEMLPParams, _moe_mlp_weights

    body = harness.load_json("configs", "keye-vl-2.0-30b-a3b.json")
    c = keye_vl2_lm_config(body, sequence_length=128)
    assert (c.num_layers, c.qk_norm, c.rope_theta, c.num_kv_heads) == (
        6, "head", 1e7, 4)
    front = AttentionFrontEnd(
        c.hidden_size, c.num_heads, False, c.rope_theta, c.qk_norm,
        c.norm_eps, c.num_kv_heads, c.head_dim, False, c.indexer)
    d = c.hidden_size
    attn = {w.name: math.prod(w.shape) for w in front.weight_specs(d, d, d)}
    assert attn["wq"] == attn["wo"] == 8_388_608
    assert attn["wk"] == attn["wv"] == 1_048_576
    assert (attn["q_norm"], attn["k_norm"]) == (128, 128)
    assert (attn["wi_q"], attn["wi_k"], attn["wi_w"]) == (
        2_097_152, 131_072, 32_768)
    moe = {w.name: math.prod(w.shape) for w in _moe_mlp_weights(
        MoEMLPParams(c.num_experts, c.num_experts_per_tok,
                     c.moe_intermediate_size, **c.moe_routing),
        [(16, 1, d)]) if w.trainable}
    assert moe["router"] == 262_144
    assert moe["gate"] + moe["up"] + moe["down"] == 603_979_776
    layer = sum(attn.values()) + sum(moe.values()) + 2 * d
    assert round(layer / 1e6, 1) == 625.4
    whole = 6 * layer + 2 * c.vocab_size * d + d
    assert round(whole / 1e6, 1) == 4374.6
    assert "4,374.6 M" in body["parameters"]["all"]
    assert "625.39 M" in body["parameters"]["layer"]
    # the cache: [k ; v] 1,024 and an indexer key of 64 a token a layer
    assert front.cache_row_widths(33536)["pool_kv"] == 1024
    assert "13,056 B" in body["parameters"]["cache_a_token"]
    assert keye2_events.attend_bytes_a_row(body, 2) == 6 * 2048
    assert keye2_events.index_bytes_a_row(body, 2) == 6 * 128


def test_the_mix_and_the_cell_are_the_issues():
    mix = harness.load_json("traffic", "serve-mediaqa.json")
    cell = harness.load_json("workloads", CELL + ".json")
    assert mix["kind"] == "closed_loop_sessions"
    assert set(mix) >= set(harness.load_json("traffic",
                                             "serve-sessions.json"))
    histories = traffic.quantiles(mix["history_tokens"], mix["clients"])
    assert len(histories) == 16 == mix["cycle"] == cell["serve"]["slots"]
    assert 8192 <= min(histories) and max(histories) <= 32768
    assert 280_000 < sum(histories) < 290_000
    assert histories == sorted(histories) and len(set(histories)) == 16
    questions, replies = traffic.request_sizes(mix)
    assert 32 <= min(questions) and max(questions) <= 128
    assert 128 <= min(replies) and max(replies) <= 512
    # the two compared streams' contexts: near 9 k and 16 k
    near = [histories[c] for c in mix["check_stream_histories"]]
    assert 8_000 < near[0] < 9_500 and 15_000 < near[1] < 16_500
    assert max(replies) <= keye_vl2_reference.ROWS
    serve = cell["serve"]
    assert (max(histories) + max(questions) + max(replies)
            <= serve["max_seq_len"])
    assert serve["max_seq_len"] == 33536 and serve["prefill_chunk"] == 256
    assert serve["prefix_cache"] is True
    # the pool holds every history and what 16 live requests draw
    bs = serve["kv_block_size"]
    need = sum(-(-h // bs) for h in histories) + 16 * 4
    assert need < serve["kv_num_blocks"] == 1440
    # bytes a token as stored: [k ; v] 1,024 + the index key's row of 128
    row = (1024 + 128) * 2 * 6
    assert 4.8e9 < serve["kv_num_blocks"] * bs * row < 5.2e9
    assert cell["kv_block_size_why"] and cell["kv_num_blocks_why"]
    assert cell["job"] == "serve_mediaqa"
    assert "--dtype" in cell["flags"] and "bf16" in cell["flags"]


def test_the_benchmarks_reference_is_the_programs():
    mine = open(keye_vl2_reference.__file__).read()
    theirs = open(program_reference.__file__).read()
    head = "builds it from `keye_vl2_lm_config`: the forward"
    body = theirs[theirs.index("float32, `jax.default_matmul"):]
    body = body.replace("from . import deepseek_v32_reference as dsa",
                        "from benchmarks import deepseek_v32_reference "
                        "as dsa")
    assert head in mine and body in mine
    assert keye_vl2_reference.LOGIT_TOL == keye_vl2_reference.CACHE_TOL == 0.02
    assert 0 < keye_vl2_reference.SEL_MARGIN < 0.1
    assert 0 < keye_vl2_reference.ROUTE_MARGIN < 0.2
    assert 0 < keye_vl2_reference.MAX_OUTSIDE <= 128
    assert keye_vl2_reference.SPOILS == program_reference.SPOILS


def test_mediaqa_job_runs_a_window_through_serve(tiny, capsys):
    assert run.main(["--workload", "tiny-mediaqa", "--seed",
                     str(2**31 + 11), "--seconds", "1.5", "--trace", "0"],
                    tiny) == 0
    out = capsys.readouterr().out
    line = result_line(out)
    assert line["correct"] is True and line["failed"] == 0, out
    assert "prompt tokens: sound" in out
    assert "0 without their whole history" in out
    assert "0 histories moved or evicted" in out and "0 dropped" in out
    assert out.count("replayed with 4 slots live") == 2
    # the reference was given the experts the prompts' tokens chose: the
    # check's prompts whole, the streams' histories at the least
    assert "chose at 11 prompt positions of 19 tokens" in out
    assert out.count("the reference is given the experts") == 4
    assert line["attempted"] >= 4
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("control", ["topk_half", "no_rope",
                                     "lost_index_block"])
def test_mediaqa_job_is_not_correct_under_a_control(tiny, capsys,
                                                    monkeypatch, control):
    """The builder's controls through the job's own hook: the reference
    with half the selection or without RoPE, and the replay with a block
    of indexer keys of every history zeroed."""
    load = harness.load_module

    def loaded(*parts):
        module = load(*parts)
        if parts == ("jobs", "serve_mediaqa.py"):
            job = module.run
            module.run = lambda ctx: job(ctx, control=control)
        return module

    monkeypatch.setattr(harness, "load_module", loaded)
    assert run.main(["--workload", "tiny-mediaqa", "--seed", "5",
                     "--seconds", "0.3", "--trace", "0"], tiny) == 0
    out = capsys.readouterr().out
    assert result_line(out)["correct"] is False, out
    if control == "lost_index_block":
        assert "indexer keys of blocks" in out


def test_traced_mediaqa_run_reads_what_it_can(tiny, capsys, monkeypatch):
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
    scoped = keye2_events.scoped_instructions
    monkeypatch.setattr(
        keye2_events, "scoped_instructions",
        lambda text: seen.setdefault("pairs", scoped(text)))
    assert run.main(["--workload", "tiny-mediaqa", "--seed", "1",
                     "--seconds", "30", "--trace", "1"], tiny) == 0
    out = capsys.readouterr().out
    line = result_line(out)
    assert line["correct"] is True, out
    assert {"prefix_hit_pct.serve", "engine_iter_ms", "chunk_step_ms.serve",
            "ffcompile_s", "xla_compile_s"} <= set(line["metrics"])
    assert not {"gsa_attend_ms.serve", "gsa_attend_roofline_pct.serve",
                "dsa_index_roofline_pct.serve"} & set(line["metrics"])
    assert line["metrics"]["prefix_hit_pct.serve"]["value"] > 50
    assert {"gsa.qkv", "gsa.attend", "gsa.out", "dsa.index", "dsa.topk",
            "moe.route", "moe.experts", "moe.combine"} <= {
        s for _, s in seen["pairs"]}


HLO = '''
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f.1, metadata={op_name="jit(decode_step)/l0_attn/dsa.index/dot_general"}
  %sort.2 = s32[8]{0} sort(%x), dimensions={0}, metadata={op_name="jit(decode_step)/l0_attn/dsa.topk/top_k"}
  %gather.3 = bf16[8,4]{1,0} gather(%y), metadata={op_name="jit(decode_step)/l0_attn/gsa.attend/gather"}
  %fusion.4 = bf16[8,4]{1,0} fusion(%z), kind=kOutput, calls=%f.2, metadata={op_name="jit(decode_step)/l0_attn/gsa.out/dot_general"}
  %fusion.5 = bf16[8,4]{1,0} fusion(%z), kind=kOutput, calls=%f.3, metadata={op_name="jit(decode_step)/l1_moe/moe.combine/mul"}
  ROOT %fusion.6 = bf16[8,4]{1,0} fusion(%z), kind=kOutput, calls=%f.4, metadata={op_name="jit(decode_step)/lm_head/dot_general"}
'''


def hand_made_run(pairs, steps):
    ms = 1_000_000
    ops = [("%fusion.1 = f32[8] fusion(%p)", 0, 2 * ms),
           ("%sort.2 = s32[8] sort(%x)", 2 * ms, 5 * ms),
           ("%gather.3 = bf16[8,4] gather(%y)", 5 * ms, 9 * ms),
           ("%fusion.4 = bf16[8,4] fusion(%z)", 9 * ms, 10 * ms),
           # the second step: twice the index, half the gather
           ("%fusion.1 = f32[8] fusion(%p)", 20 * ms, 24 * ms),
           ("%gather.3 = bf16[8,4] gather(%y)", 24 * ms, 26 * ms),
           ("%gmm.7 = bf16[8,4] custom-call(%a)", 26 * ms, 29 * ms),
           ("%fusion.6 = bf16[8,4] fusion(%z)", 29 * ms, 30 * ms),
           # a chunk step's events: another step's interval, left out
           ("%fusion.1 = f32[8] fusion(%p)", 40 * ms, 49 * ms)]
    r = types.SimpleNamespace(
        result={"counters": {"keye2_instructions": pairs}},
        config={"num_hidden_layers": 2, "num_key_value_heads": 4,
                "head_dim": 128, "sa_config": {"indexer_head_dim": 64}},
        peaks={"hbm_bytes_per_s": 8.0e11})
    r.trace = trace.Trace([trace.Chip(0, ops, [])], [], (0, 60 * ms))
    r.device_steps = device_steps.Record(
        steps, len(steps), {}, 0.0, (0.0, 0.0), [], [])
    return r


def a_step(i, kind, start, end, **args):
    ms = 1_000_000
    return device_steps.Step(
        id=i, kind=kind, bucket=0, chunk_start=0, rows=16, start=start * ms,
        end=end * ms, busy_ns=0.0, idle_before_ns=0.0, args=args)


def test_the_new_readers_on_hand_made_events():
    """Two pure-decode steps and a chunk step: the readers take the events
    inside the device's own intervals of the decode steps, by scope, and
    hold them to the bytes the steps' own arguments count."""
    pairs = keye2_events.scoped_instructions(HLO)
    assert pairs == [["fusion.1", "dsa.index"], ["sort.2", "dsa.topk"],
                     ["gather.3", "gsa.attend"], ["fusion.4", "gsa.out"],
                     ["fusion.5", "moe.combine"]]
    args = dict(kv_itemsize=2, sel_rows=16 * 2048, index_rows=16 * 20000)
    steps = [a_step(1, "decode", 0, 10, **args),
             a_step(2, "decode", 20, 30, **args),
             a_step(3, "chunk", 40, 50, **args)]
    r = hand_made_run(pairs, steps)
    read = lambda name: harness.load_reader(name).read(r)  # noqa: E731
    assert read("gsa_attend_ms.serve") == pytest.approx(3.0)
    found = keye2_events.by_scope(r)
    assert found["dsa.index"] == pytest.approx(6e-3)
    assert found["dsa.topk"] == pytest.approx(3e-3)
    assert found["moe.experts"] == pytest.approx(3e-3)   # the gmm call
    assert found["other"] == pytest.approx(1e-3) and len(found["steps"]) == 2
    # by hand: 2 steps x 16 x 2,048 rows x (2 layers x 2 x 4 x 128 x 2 B)
    # = 268,435,456 B at 8e11 B/s = 335.5 us, over 6 ms of gsa.attend
    assert read("gsa_attend_roofline_pct.serve") == pytest.approx(
        100 * 268_435_456 / 8.0e11 / 6e-3)
    # 2 steps x 320,000 keys x (2 layers x 64 x 2 B) = 163,840,000 B =
    # 204.8 us, over 6 ms of dsa.index
    assert read("dsa_index_roofline_pct.serve") == pytest.approx(
        100 * 163_840_000 / 8.0e11 / 6e-3)
    assert read("gsa_attend_roofline_pct.serve") < 100


def test_the_new_readers_find_nothing_on_a_parent_or_a_bad_join():
    pairs = keye2_events.scoped_instructions(HLO)
    steps = [a_step(1, "decode", 0, 10, kv_itemsize=2)]  # no counts
    names = ("gsa_attend_ms.serve", "gsa_attend_roofline_pct.serve",
             "dsa_index_roofline_pct.serve")
    no_counts = hand_made_run(pairs, steps)
    assert harness.load_reader(names[0]).read(no_counts) == pytest.approx(4.0)
    assert harness.load_reader(names[1]).read(no_counts) is None
    assert harness.load_reader(names[2]).read(no_counts) is None
    no_pairs = hand_made_run(None, steps)
    unjoined = hand_made_run(pairs, steps)
    unjoined.device_steps.dispatched = 2    # one step was not joined
    nothing = hand_made_run(pairs, steps)
    nothing.device_steps = None             # a program without `step` ids
    for r in (no_pairs, unjoined, nothing):
        for name in names:
            assert harness.load_reader(name).read(r) is None, name
