"""The job, traffic, reference and readers of `dsv32-serve-sessions` (PR
31) on the CPU at tiny widths, as test_olmoe_cells.py does it for PR 27's:
the real sizes run only on the chip.
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
        "benchmarks_run_dsv32", os.path.join(REPO, "benchmarks", "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load_run()
from benchmarks import (  # noqa: E402
    deepseek_v32_reference, dsv32_events, harness, trace, traffic,
)
from flexflow_tpu.models import (  # noqa: E402
    deepseek_v32_reference as program_reference,
)

with open("/opt/skills/guides/model-configs/architectures.jsonl"
          if os.path.exists(
              "/opt/skills/guides/model-configs/architectures.jsonl")
          else os.devnull) as _f:
    _rows = [json.loads(line) for line in _f if "DeepSeek-V3.2\"" in line]
# the catalog row's config, key for key (kept here: the catalog is not
# part of the repository)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v32", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 129280}
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 16, "vocab_size": 16160,
           "num_nextn_predict_layers": 0}
TINY = {
    **PUBLISHED, "source": "the test file", "hidden_size": 64,
    "num_attention_heads": 4, "n_embd": 64, "n_head": 4, "q_lora_rank": 24,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "index_n_heads": 2, "index_head_dim": 16,
    "index_topk": 8, "intermediate_size": 96, "moe_intermediate_size": 24,
    "n_routed_experts": 4, "experts_held": [4, 4], "n_group": 4,
    "topk_group": 2, "num_experts_per_tok": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "vocab_size": 97,
    "rope_scaling": {**PUBLISHED["rope_scaling"],
                     "original_max_position_embeddings": 16},
    "initializer_range": 0.1, "reduced": ["n_routed_experts"],
    "experts_routed": 16, "reduced_from": {"n_routed_experts": 16}}
TINY_SESSIONS = {
    "kind": "closed_loop_sessions", "clients": 4, "cycle": 4,
    "history_tokens": {"dist": "log_uniform", "min": 10, "max": 30},
    "prompt_tokens": {"dist": "log_uniform", "min": 3, "max": 8},
    "new_tokens": {"dist": "uniform", "min": 2, "max": 5},
    "check_history_tokens": [11, 14], "check_stream_histories": [0, 3]}
FLAGS = ["--mesh", "1,1,1,1", "--no-verify-plan"]


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    files = {
        "configs/tiny-dsv32.json": TINY,
        "traffic/tiny-sessions.json": TINY_SESSIONS,
        "workloads/tiny-sessions.json": {
            "job": "serve_sessions", "flags": FLAGS, "optimizer": "sgd",
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
        {"name": "tiny-sessions", "config": "tiny-dsv32",
         "traffic": "tiny-sessions", "chips": 1, "why": "test"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (["tiny-sessions"]
                              if "dsv32-serve-sessions" in m["workloads"]
                              or m["name"] == "tpot_ms.p90" else [])
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
    body = harness.load_json("configs", "deepseek-v3.2.json")
    for key, value in PUBLISHED.items():
        assert body[key] == REDUCED.get(key, value), key
    if _rows:  # the catalog, where it is at hand
        assert _rows[0]["config"] == PUBLISHED
        assert _rows[0]["source_url"] == body["source"]
    assert body["reduced"] == list(REDUCED)
    assert body["reduced_from"] == {k: PUBLISHED[k] for k in REDUCED}
    assert body["experts_held"] == [0, 16]
    assert body["experts_routed"] == PUBLISHED["n_routed_experts"]
    assert (body["n_embd"], body["n_head"]) == (7168, 128)
    for key in ("indexer_rope", "index_norm_eps", "e_score_correction_bias",
                "initializer_range"):
        assert key in body["assumed"], key
    assert len(body["departures"]) >= 5 and "16 chips" in body["deployment"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = run.manifest_entry(manifest, "configs", "deepseek-v3.2")
    assert entry["source"] == body["source"]
    assert entry["reduced"] == body["reduced"]
    cell = run.manifest_entry(manifest, "workloads", "dsv32-serve-sessions")
    assert cell["chips"] == 1 and cell["traffic"] == "serve-sessions"
    reports = {m["name"] for m in run.metrics_of(
        manifest, "per_layer", "dsv32-serve-sessions")}
    assert {"dsa_index_ms.serve", "mla_attend_ms.serve", "moe_ms.serve",
            "prefix_hit_pct.serve", "engine_iter_ms", "chunk_step_ms.serve",
            "device_idle_pct.serve", "engine_idle_ms.fetch"} <= reports
    # `tpot_ms.p90` spreads 5 % over six seeds here, twice what admits a
    # cell (PERF.md section 6, PR 31): the cell does not report it, nor
    # `prefill_share_pct`, which moves it
    assert not {"paged_decode_ms.serve", "paged_decode_roofline_pct.serve",
                "prefill_share_pct"} & reports
    assert [m["name"] for m in run.metrics_of(
        manifest, "end_to_end", "dsv32-serve-sessions")] == [
            "serve_tok_s", "setup_s"]


def test_the_mix_and_the_cell_are_the_issues():
    mix = harness.load_json("traffic", "serve-sessions.json")
    cell = harness.load_json("workloads", "dsv32-serve-sessions.json")
    histories = traffic.quantiles(mix["history_tokens"], mix["clients"])
    assert len(histories) == 16 == mix["cycle"] == cell["serve"]["slots"]
    assert 8192 <= min(histories) and max(histories) <= 32768
    assert 280_000 < sum(histories) < 290_000
    turns, replies = traffic.request_sizes(mix)
    assert 64 <= min(turns) and max(turns) <= 256
    assert 32 <= min(replies) and max(replies) <= 128
    serve = cell["serve"]
    assert max(histories) + max(turns) + max(replies) <= serve["max_seq_len"]
    assert serve["max_seq_len"] == 33280 and serve["prefill_chunk"] == 256
    # the pool holds every history and what 16 live requests draw
    bs = serve["kv_block_size"]
    need = sum(-(-h // bs) for h in histories) + 16 * 3
    assert need < serve["kv_num_blocks"]
    # bytes a token: the latent row (576, stored in 640) + the index key
    row = (640 + 128) * 2 * 5
    assert serve["kv_num_blocks"] * bs * row < 3.0e9
    assert cell["kv_block_size_why"] and cell["kv_num_blocks_why"]
    assert "--dtype" in cell["flags"] and "bf16" in cell["flags"]


def test_the_benchmarks_reference_is_the_programs():
    mine = open(deepseek_v32_reference.__file__).read()
    theirs = open(program_reference.__file__).read()
    head = "builds it from `deepseek_v32_lm_config`: the forward pass"
    body = theirs[theirs.index("float32, `jax.default_matmul"):]
    assert head in mine and body in mine
    assert deepseek_v32_reference.LOGIT_TOL == 0.03
    assert 0 < deepseek_v32_reference.SEL_MARGIN < 0.1
    assert 0 < deepseek_v32_reference.ROUTE_MARGIN < 0.1


def test_a_selection_is_allowed_up_to_its_two_limits():
    """`select`'s rule for the program's set and the two readings that say
    how far a row is from refused: 6 positions, top-3, one head."""
    import jax.numpy as jnp

    ref = deepseek_v32_reference
    # row 5 scores its past 1.0, 0.9, 0.8, 0.5, 0.1, 0.0: the 3rd is 0.8
    qi = jnp.ones((6, 1, 1))
    wt = jnp.ones((6, 1))
    ki = jnp.asarray([[1.0], [0.9], [0.8], [0.5], [0.1], [0.0]])

    def held(chosen, **kw):
        _, taken, bad, readings = ref.select(
            qi, wt, ki, 3, program_sel={5: np.asarray(chosen)}, **kw)
        return taken, bad, readings.get(5)

    # the reference's own set: nothing outside, taken only at a near-tie
    assert held([0, 1, 2], margin=0.05) == ([], [], (0, 0.0))
    assert held([0, 1, 2], margin=0.35)[0] == [5]
    # position 3 for 2: 0.3 under the 3rd, outside a margin of 0.05 ...
    taken, bad, (outside, shortfall) = held([0, 1, 3], margin=0.05)
    assert (taken, bad, outside) == ([], [5], 1)
    assert shortfall == pytest.approx(0.3)
    # ... allowed and taken where one position may be, or the margin is 0.35
    taken, bad, (outside, shortfall) = held([0, 1, 3], margin=0.05,
                                            max_outside=1)
    assert (taken, bad, outside, shortfall) == ([5], [], 1, 0.0)
    assert held([0, 1, 3], margin=0.35)[:2] == ([5], [])
    # two of three positions low: the second lowest decides the shortfall
    taken, bad, (outside, shortfall) = held([0, 3, 4], margin=0.05,
                                            max_outside=1)
    assert (bad, outside) == ([5], 2) and shortfall == pytest.approx(0.3)
    # a set with a position twice, or fewer than top-k, is never allowed
    assert held([0, 1, 1], margin=0.5, max_outside=3)[1] == [5]
    assert held([0, 1, -1], margin=0.5, max_outside=3)[1] == [5]


def test_sessions_job_runs_a_window_through_serve(tiny, capsys):
    assert run.main(["--workload", "tiny-sessions", "--seed",
                     str(2**31 + 11), "--seconds", "1.5", "--trace", "0"],
                    tiny) == 0
    out = capsys.readouterr().out
    line = result_line(out)
    assert line["correct"] is True and line["failed"] == 0, out
    assert "prompt tokens: sound" in out
    assert "0 without their whole history" in out
    assert "0 histories moved or evicted" in out and "0 dropped" in out
    # two served streams replayed for their logits, every slot live
    assert out.count("replayed with 4 slots live") == 2
    assert line["attempted"] >= 4
    assert set(line["metrics"]) == {"serve_tok_s", "tpot_ms.p90", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_sessions_job_calls_what_it_compiled_ahead(tiny, capsys, caplog):
    """Set-up lowers every program of the run and compiles them in threads
    (`Ahead`, `lower_step`, the reference's `lowerings`); a call then finds
    its program and lowers nothing again. A program lowered for other
    shapes, or for an argument that lies elsewhere than the call's, would
    be compiled twice, the second time in the run's own time."""
    import logging

    import jax

    jax.clear_caches()
    jax.config.update("jax_log_compiles", True)
    try:
        with caplog.at_level(logging.WARNING):
            assert run.main(["--workload", "tiny-sessions", "--seed", "9",
                             "--seconds", "0.3", "--trace", "0"], tiny) == 0
    finally:
        jax.config.update("jax_log_compiles", False)
    assert result_line(capsys.readouterr().out)["correct"] is True
    lowered = [r.getMessage().split("Compiling ")[1].split(" ")[0]
               for r in caplog.records if "Compiling jit(" in r.getMessage()]
    # all of the tiny run's sequences are padded to one length
    for name in ("_embed", "_attention_inputs", "_select_all", "_named_rows",
                 "_set_rows", "_attend", "_dense_tail", "_expert_tail",
                 "_head"):
        assert lowered.count(f"jit({name})") == 1, (name, lowered)
    # the engine's step and the logits step are lowered before the first
    # history is prefilled, never by a call (the pool's copy program, which
    # is called after the histories, comes later than all of them)
    steps = [i for i, n in enumerate(lowered)
             if n in ("jit(decode_step)", "jit(step_logits)")]
    assert len(steps) >= 4 and max(steps) < lowered.index("jit(copy_blocks)")


def test_sessions_job_is_not_correct_when_the_logits_are_off(
        tiny, capsys, monkeypatch):
    monkeypatch.setattr(deepseek_v32_reference, "LOGIT_TOL", 1e-12)
    assert run.main(["--workload", "tiny-sessions", "--seed", "3",
                     "--seconds", "0.3", "--trace", "0"], tiny) == 0
    assert result_line(capsys.readouterr().out)["correct"] is False


def test_sessions_job_is_not_correct_when_a_history_moves(
        tiny, capsys, monkeypatch):
    """A history that does not lie, after the window, in the blocks set-up
    left it in (evicted, prefilled again) is another workload."""
    load = harness.load_module

    def loaded(*parts):
        module = load(*parts)
        if parts == ("jobs", "serve_sessions.py"):
            blocks, calls = module.history_blocks, []

            def history_blocks(engine, histories):
                calls.append(1)
                found = blocks(engine, histories)
                if len(calls) > 1:  # after the window
                    found[2] = (found[2][0], found[2][1][::-1])
                return found

            module.history_blocks = history_blocks
        return module

    monkeypatch.setattr(harness, "load_module", loaded)
    assert run.main(["--workload", "tiny-sessions", "--seed", "5",
                     "--seconds", "0.3", "--trace", "0"], tiny) == 0
    out = capsys.readouterr().out
    assert "1 histories moved or evicted" in out
    assert result_line(out)["correct"] is False


def test_traced_sessions_run_reads_what_it_can(tiny, capsys, monkeypatch):
    """--trace 1 with the trace steered to the recorded GPT-2 one (the CPU
    has no device plane): the job compiles the decode step's text for the
    scoped instructions, the readers find no `ff/serve.step` span in that
    trace and leave the three device metrics out; the counters' metrics
    are there."""
    import jax

    with open(os.path.join(HERE, "recorded_trace.textproto")) as f:
        recorded = trace.read(
            jax.profiler.ProfileData.from_text_proto(f.read()))
    monkeypatch.setattr(trace, "read_file", lambda path: recorded)
    seen = {}
    scoped = dsv32_events.scoped_instructions
    monkeypatch.setattr(
        dsv32_events, "scoped_instructions",
        lambda text: seen.setdefault("pairs", scoped(text)))
    assert run.main(["--workload", "tiny-sessions", "--seed", "1",
                     "--seconds", "30", "--trace", "1"], tiny) == 0
    out = capsys.readouterr().out
    line = result_line(out)
    assert line["correct"] is True, out
    assert {"prefix_hit_pct.serve", "engine_iter_ms", "chunk_step_ms.serve",
            "ffcompile_s", "xla_compile_s"} <= set(line["metrics"])
    assert line["metrics"]["prefix_hit_pct.serve"]["value"] > 50
    assert {"mla.q", "mla.kv", "dsa.index", "dsa.topk", "mla.attend",
            "mla.out", "moe.route", "moe.experts", "moe.shared"} <= {
        s for _, s in seen["pairs"]}


HLO = '''
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f.1, metadata={op_name="jit(decode_step)/l0_attn/dsa.index/dot_general"}
  %sort.2 = s32[8]{0} sort(%x), dimensions={0}, metadata={op_name="jit(decode_step)/l0_attn/dsa.topk/top_k"}
  %gather.3 = bf16[8,4]{1,0} gather(%y), metadata={op_name="jit(decode_step)/l0_attn/mla.attend/gather"}
  %fusion.4 = bf16[8,4]{1,0} fusion(%z), kind=kOutput, calls=%f.2, metadata={op_name="jit(decode_step)/l0_attn/mla.out/dot_general"}
  %fusion.5 = bf16[8,4]{1,0} fusion(%z), kind=kOutput, calls=%f.3, metadata={op_name="jit(decode_step)/l1_moe/moe.shared/dot_general"}
  ROOT %fusion.6 = bf16[8,4]{1,0} fusion(%z), kind=kOutput, calls=%f.4, metadata={op_name="jit(decode_step)/lm_head/dot_general"}
'''


def test_serve_readers_on_hand_made_events():
    pairs = dsv32_events.scoped_instructions(HLO)
    assert pairs == [["fusion.1", "dsa.index"], ["sort.2", "dsa.topk"],
                     ["gather.3", "mla.attend"], ["fusion.4", "mla.out"],
                     ["fusion.5", "moe.shared"]]
    ms = 1_000_000
    ops = [("%fusion.1 = f32[8] fusion(%p)", 0, 2 * ms),
           ("%sort.2 = s32[8] sort(%x)", 2 * ms, 5 * ms),
           ("%gather.3 = bf16[8,4] gather(%y)", 5 * ms, 9 * ms),
           ("%fusion.4 = bf16[8,4] fusion(%z)", 9 * ms, 10 * ms),
           ("%gmm.7 = bf16[8,4] custom-call(%a)", 10 * ms, 16 * ms),
           ("%fusion.5 = bf16[8,4] fusion(%z)", 16 * ms, 18 * ms),
           ("%fusion.6 = bf16[8,4] fusion(%z)", 18 * ms, 20 * ms),
           # a chunk step's events: another program's names, left out
           ("%fusion.1 = f32[8] fusion(%p)", 30 * ms, 39 * ms)]
    r = types.SimpleNamespace(
        result={"counters": {"decode_instructions": pairs}})
    r.trace = trace.Trace([trace.Chip(0, ops, [])], [], (0, 50 * ms))
    r.program_spans = [("ff/serve.step", 0, 10 * ms, {}),
                       ("ff/serve.step", 10 * ms, 21 * ms, {}),
                       ("ff/serve.prefill", 29 * ms, 40 * ms, {})]
    read = lambda name: harness.load_reader(name).read(r)  # noqa: E731
    assert read("dsa_index_ms.serve") == pytest.approx(2.5)
    assert read("mla_attend_ms.serve") == pytest.approx(2.0)
    assert read("moe_ms.serve") == pytest.approx(4.0)
    found = dsv32_events.by_scope(r)
    assert found["mla.out"] == pytest.approx(1e-3)
    assert found["other"] == pytest.approx(2e-3) and found["steps"] == 2
    nothing = types.SimpleNamespace(result={"counters": {}}, trace=r.trace,
                                    program_spans=r.program_spans)
    parent = types.SimpleNamespace(
        result={"counters": {"decode_instructions": pairs}}, trace=r.trace,
        program_spans=[])
    for name in ("dsa_index_ms.serve", "mla_attend_ms.serve",
                 "moe_ms.serve"):
        assert harness.load_reader(name).read(nothing) is None
        assert harness.load_reader(name).read(parent) is None
    r.result["counters"]["prefix_hit_pct"] = 98.7
    assert read("prefix_hit_pct.serve") == 98.7
    assert harness.load_reader("prefix_hit_pct.serve").read(nothing) is None
