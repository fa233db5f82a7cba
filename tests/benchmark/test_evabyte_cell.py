"""The configuration, job, traffic, reference and readers of
`evabyte-serve-bytedocs` (PR 53) on the CPU at tiny widths, as
test_cmdap_cell.py does it for PR 49's: the real sizes run only on the
chip. Nothing here reads the process-wide compile log: what a run compiled
and when is the chip's to say.
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
        "benchmarks_run_evabyte", os.path.join(REPO, "benchmarks", "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


run = _load_run()
from benchmarks import (  # noqa: E402
    device_steps, evabyte_events, evabyte_reference, harness, trace, traffic,
)
from flexflow_tpu.models import (  # noqa: E402
    evabyte_lm_config, evabyte_reference as program_reference,
)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
with open(CATALOG if os.path.exists(CATALOG) else os.devnull) as _f:
    _rows = [json.loads(line) for line in _f if '"EvaByte"' in line]
# the catalog row's config, key for key (kept here: the catalog is not
# part of the repository)
PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048}
REDUCED = {"num_hidden_layers": 8}
# hidden 64, 4 heads of 16, a window of 16 in chunks of 4, two layers
TINY = {
    **PUBLISHED, "source": "the test file", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4, "n_embd": 64,
    "n_head": 4, "intermediate_size": 96, "window_size": 16, "chunk_size": 4,
    "num_hidden_layers": 2, "vocab_size": 67, "init_std": 0.1,
    "reduced": ["num_hidden_layers"], "reduced_from": {
        "num_hidden_layers": 32}}
# histories of 18, 25, 36 and 51 over a window of 16: one to three closed
# windows each
TINY_BYTEDOCS = {
    "kind": "closed_loop_sessions", "clients": 4, "cycle": 4,
    "history_tokens": {"dist": "log_uniform", "min": 15, "max": 60},
    "prompt_tokens": {"dist": "log_uniform", "min": 6, "max": 8},
    "new_tokens": {"dist": "uniform", "min": 4, "max": 12},
    "check_history_tokens": [37], "check_stream_histories": [0, 3]}
FLAGS = ["--mesh", "1,1,1,1", "--no-verify-plan"]
CELL = "evabyte-serve-bytedocs"
CONFIG = "evabyte-6.5b"
NEW = ("eva_attend_ms.serve", "eva_attend_roofline_pct.serve",
       "eva_summarise_ms.serve", "eva_summary_rows_pct.serve")


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    files = {
        "configs/tiny-evabyte.json": TINY,
        "traffic/tiny-bytedocs.json": TINY_BYTEDOCS,
        "workloads/tiny-bytedocs.json": {
            "job": "serve_bytedocs", "flags": FLAGS, "optimizer": "sgd",
            "attention_impl": "xla", "train_batch": 1,
            "train_sequence_length": 16, "trace_seconds": 1,
            "serve": {"slots": 4, "max_seq_len": 80, "prefill_chunk": 8,
                      "kv_layout": "paged", "kv_block_size": 8,
                      "kv_num_blocks": 96, "kv_window_blocks": 64,
                      "prefix_cache": True}},
    }
    for rel, body in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(body))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["workloads"] = [
        {"name": "tiny-bytedocs", "config": "tiny-evabyte",
         "traffic": "tiny-bytedocs", "chips": 1, "why": "test"}]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (["tiny-bytedocs"] if CELL in m["workloads"]
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
    assert body["assumed"] == program_reference.ASSUMED
    assert set(body["assumed"]) == {"summary_logit", "summary_position",
                                    "summary_visibility", "norm_statistics"}
    assert body["departures"] == list(program_reference.DEPARTURES.values())
    assert "four pipeline stages of 8 layers" in body["deployment"]
    assert "stage 0" in body["deployment"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = run.manifest_entry(manifest, "configs", CONFIG)
    assert entry["source"] == body["source"]
    assert entry["reduced"] == body["reduced"] == ["num_hidden_layers"]
    cell = run.manifest_entry(manifest, "workloads", CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve-bytedocs"
    assert cell["why"] == (
        "16 byte sessions, cached histories 4-27 k, turns 128-1,024, replies "
        "512-2,048, greedy: a row reads its aligned 2,048-key window and 128 "
        "summaries a closed window, 8 MHA layers; 1 of 4 stages")
    assert len(cell["why"]) < 200
    reports = {m["name"] for m in run.metrics_of(manifest, "per_layer", CELL)}
    assert {*NEW, "kv_bytes_a_token.serve", "prefix_hit_pct.serve",
            "chunk_step_ms.serve", "engine_iter_ms", "device_idle_pct.serve",
            "device_step_ms.decode.serve", "device_step_ms.chunk.serve",
            "chunk_share_pct.serve", "host_iter_ms.serve",
            "host_stage_ms.serve", "step_join_pct.serve",
            "steps_ahead_pct.serve", "engine_idle_ms.fetch",
            "setup_programs", "ffcompile_s", "xla_compile_s",
            # the core's calls are the paged kernels': their readers match
            # by the kernels' names
            "paged_decode_ms.serve", "paged_chunk_ms.serve"} <= reports
    assert not {"moe_ms.serve", "swa_decode_ms.serve", "gqa_decode_ms.serve",
                "paged_decode_roofline_pct.serve",
                "prefill_share_pct"} & reports
    assert [m["name"] for m in run.metrics_of(
        manifest, "end_to_end", CELL)] == ["serve_tok_s", "setup_s"]
    for name in NEW:  # the new readers are this cell's alone
        entry = run.manifest_entry(manifest, "per_layer", name)
        assert entry["workloads"] == [CELL]
        assert (entry["layer"], entry["moves"]) == ("kernels", "serve_tok_s")


def test_the_parameter_table_is_the_programs_weight_shapes():
    """The configuration file's arithmetic against the shapes the ops
    declare for the published keys (no array is made)."""
    from flexflow_tpu.ops.attention import AttentionFrontEnd

    body = harness.load_json("configs", CONFIG + ".json")
    c = evabyte_lm_config(body, sequence_length=128)
    assert c.layer_pattern == ("swa",) * 8
    assert c.swa == dict(window=2048, summary_chunk=16)
    assert (c.norm_unit_offset, c.fp32_residual, c.fp32_logits) == (
        True, True, True)
    assert (c.vocab_size, c.rope_theta, c.initializer_range) == (
        320, 100000.0, 0.01275)
    d = c.hidden_size
    front = AttentionFrontEnd(d, c.num_heads, False,
                              rope_theta=c.rope_theta, **c.swa)
    count = {w.name: math.prod(w.shape) for w in front.weight_specs(d, d, d)}
    assert count == {"wq": 4096 * 4096, "wk": 4096 * 4096, "wv": 4096 * 4096,
                     "wo": 4096 * 4096, "phi": 32 * 128, "mu_k": 32 * 128}
    assert round(sum(count.values()) / 1e6, 2) == 67.12
    mlp = 3 * d * c.intermediate_size
    assert round(mlp / 1e6, 2) == 135.27
    layer = sum(count.values()) + mlp + 2 * d
    assert round(layer / 1e6, 2) == 202.39
    whole = 8 * layer + 2 * c.vocab_size * d + d
    assert round(whole / 1e6, 1) == 1621.8
    assert "1,621.8 M = 3.24 GB" in body["parameters"]["all"]
    uncut = 32 * layer + c.vocab_size * d + 8 * d * c.vocab_size + d
    assert round(uncut / 1e6) == 6488
    # an exact row and a summary row are as wide: 16,384 B a layer in bf16
    assert front.cache_row_widths(30208) == {"pool_k": 4096, "pool_v": 4096}
    assert evabyte_events.row_bytes(body, 2) == 8 * 16384
    assert "16,384 B a layer" in body["parameters"]["exact_row"]
    assert "8,192 B a byte" in body["parameters"]["cache_a_byte"]


def test_the_mix_and_the_cell_are_the_issues():
    mix = harness.load_json("traffic", "serve-bytedocs.json")
    cell = harness.load_json("workloads", CELL + ".json")
    body = harness.load_json("configs", CONFIG + ".json")
    assert mix["kind"] == "closed_loop_sessions"
    assert set(mix) >= set(harness.load_json("traffic",
                                             "serve-sessions.json"))
    window, chunk = body["window_size"], body["chunk_size"]
    histories = traffic.quantiles(mix["history_tokens"], mix["clients"])
    assert len(histories) == 16 == mix["cycle"] == cell["serve"]["slots"]
    assert histories == [4353, 4916, 5551, 6269, 7080, 7996, 9030, 10198,
                         11516, 13006, 14688, 16587, 18732, 21155, 23891,
                         26980]
    assert sum(histories) == 201948
    assert [h // window for h in (histories[0], histories[-1])] == [2, 13]
    assert sum(h // window * (window // chunk) for h in histories) == 11776
    turns, replies = traffic.request_sizes(mix)
    assert (min(turns), max(turns)) == (137, 960)
    assert (min(replies), max(replies)) == (560, 2000)
    assert round(sum(turns) / 16) == 431 and sum(replies) // 16 == 1280
    longest = max(histories) + max(turns) + max(replies)
    assert longest == 29940 < body["max_position_embeddings"]
    # the two compared streams' distances to their window boundaries
    near = [histories[c] for c in mix["check_stream_histories"]]
    assert near == [7996, 18732]
    assert [window - h % window for h in near] == [196, 1748]
    assert near[1] % window == 300
    # the first is crossed in the turn's chunk or the first decoded rows of
    # every request, the second only where turn and reply pass 1,748
    assert 196 < min(turns) + min(replies)
    assert min(turns) + min(replies) < 1748 < max(turns) + max(replies)
    assert mix["check_history_tokens"] == [4700]
    assert (4700 // window * (window // chunk), 4700 % window) == (256, 604)
    serve = cell["serve"]
    assert longest <= serve["max_seq_len"] == 30208
    assert serve["prefill_chunk"] == 256 and serve["prefix_cache"] is True
    bs = serve["kv_block_size"]
    assert bs in (128, 256) and window % bs == 0 and bs % chunk == 0
    assert serve["max_seq_len"] % bs == 0
    # the global pool holds every history's summaries and what 16 live
    # requests draw; the window pool what the histories pin (the blocks of
    # their current window) beside the slots' reservations
    from flexflow_tpu.serving.paged import window_slot_blocks

    live = -(-(max(turns) + max(replies)) // bs) + 1
    need = sum(-(-h // bs) for h in histories) + 16 * live
    assert need < serve["kv_num_blocks"]
    pinned = sum(-(-(h % window) // bs) for h in histories)
    slot_blocks = window_slot_blocks(window, 256, bs, aligned=True)
    assert (pinned, slot_blocks) == (61, 10) if bs == 256 else True
    assert pinned + 16 * slot_blocks < serve["kv_window_blocks"]
    # under a sliding window the same traffic would not fit
    sliding = (sum(min(-(-h // bs), -(-window // bs) + 1) for h in histories)
               + 16 * window_slot_blocks(window, 256, bs))
    assert sliding > serve["kv_window_blocks"] * 1.3
    row = evabyte_events.row_bytes(body, 2)
    pools = (serve["kv_window_blocks"] * bs * row
             + serve["kv_num_blocks"] * (bs // chunk) * row)
    assert 0.25 * 16.9e9 < pools + 3.24e9 < 16.0e9
    assert cell["kv_block_size_why"] and cell["kv_window_blocks_why"]
    assert cell["job"] == "serve_bytedocs"
    assert "--dtype" in cell["flags"] and "bf16" in cell["flags"]


def test_the_benchmarks_reference_is_the_programs():
    mine = open(evabyte_reference.__file__).read()
    theirs = open(program_reference.__file__).read()
    body = theirs[theirs.index("float32, `jax.default_matmul"):]
    body = body.replace("import jax.numpy as jnp\n",
                        "import jax.numpy as jnp\nimport numpy as np\n")
    assert "The benchmark's own copy" in mine and body in mine
    assert 0 < evabyte_reference.LOGIT_TOL < 0.1
    assert 0 < evabyte_reference.CACHE_TOL < 0.1
    assert 0.01 < evabyte_reference.STREAM_SHARE < 0.1
    assert evabyte_reference.SPOILS == program_reference.SPOILS
    assert {"summaries_early", "sliding_window", "no_mu_k", "chunk_mean",
            "unrotated_summaries", "two_softmaxes", "no_summaries",
            "full_causal", "norm_no_offset", "bf16_residual",
            "e4m3"} == set(program_reference.SPOILS[1:])


def test_the_row_and_byte_counts_against_a_hand_count():
    body = harness.load_json("configs", CONFIG + ".json")
    rows = evabyte_events.rows_attended
    # window 0 is plain causal attention; the first row of a window sees
    # its own key and 128 summaries a closed window
    assert rows(0, body) == (1, 0) and rows(2047, body) == (2048, 0)
    assert rows(2048, body) == (1, 128) and rows(4700, body) == (605, 256)
    assert rows(32767, body) == (2048, 15 * 128)
    assert max(sum(rows(t, body)) for t in (32767, 30000)) == 3968
    assert evabyte_events.step_rows([0, 2048, 4700], body) == (607, 384)
    # the front end's own count is the same, with what the step writes
    from flexflow_tpu.ops.attention import AttentionFrontEnd

    front = AttentionFrontEnd(4096, 32, False, rope_theta=1e5, window=2048,
                              summary_chunk=16)
    assert front.step_counts([0, 2048, 4700, 4111]) == {
        "eva_exact_rows": 607 + 16, "eva_summary_rows": 384 + 256,
        "eva_summaries_written": 1, "eva_rollovers": 1}
    # 16,384 B a row a layer in bf16, exact or summary
    assert evabyte_events.row_bytes(body, 2) == 8 * 2 * 32 * 128 * 2


def test_bytedocs_job_runs_a_window_through_serve(tiny, capsys):
    assert run.main(["--workload", "tiny-bytedocs", "--seed",
                     str(2**31 + 11), "--seconds", "0.6", "--trace", "0"],
                    tiny) == 0
    out = capsys.readouterr().out
    line = result_line(out)
    assert line["correct"] is True and line["failed"] == 0, out
    assert "prompt tokens: sound" in out
    assert "0 without their whole history" in out
    assert "0 histories moved or evicted" in out
    assert out.count("replayed with 4 slots live") == 2
    assert out.count("rows of a served prompt") == 2
    # a line a comparison, and the run's
    assert out.count("of what a bfloat16 residual stream moves") == 4
    assert "window blocks given back" in out
    assert line["attempted"] >= 4
    assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("control", [
    "summaries_early", "two_softmaxes", "bf16_residual",
    "lost_window_block", "lost_summary_block"])
def test_bytedocs_job_is_not_correct_under_a_control(tiny, capsys,
                                                     monkeypatch, control):
    """The builder's controls through the job's own hook: spoils of the
    reference (every one moves the reference's logits:
    tests/test_evabyte.py), and the replay with the window blocks, or the
    summary blocks, of every history zeroed."""
    load = harness.load_module

    def loaded(*parts):
        module = load(*parts)
        if parts == ("jobs", "serve_bytedocs.py"):
            job = module.run
            module.run = lambda ctx: job(ctx, control=control)
        return module

    monkeypatch.setattr(harness, "load_module", loaded)
    assert run.main(["--workload", "tiny-bytedocs", "--seed", "5",
                     "--seconds", "0.3", "--trace", "0"], tiny) == 0
    assert result_line(capsys.readouterr().out)["correct"] is False


def test_the_bf16_stream_control_builds_the_other_program(tiny, capsys,
                                                         monkeypatch):
    """`bf16_stream` is a control of the PROGRAM: the job builds it with
    `fp32_skip_add` false and holds it to the same reference (in float32,
    as here, the two programs are one: the run is correct)."""
    import flexflow_tpu.models as models

    built, build = [], models.evabyte_lm_config
    monkeypatch.setattr(
        models, "evabyte_lm_config",
        lambda *a, **kw: built.append(build(*a, **kw)) or built[-1])
    load = harness.load_module

    def loaded(*parts):
        module = load(*parts)
        if parts == ("jobs", "serve_bytedocs.py"):
            job = module.run
            module.run = lambda ctx: job(ctx, control="bf16_stream")
        return module

    monkeypatch.setattr(harness, "load_module", loaded)
    assert run.main(["--workload", "tiny-bytedocs", "--seed", "6",
                     "--seconds", "0.3", "--trace", "0"], tiny) == 0
    assert [c.fp32_residual for c in built] == [False]
    assert result_line(capsys.readouterr().out)["correct"] is True


def test_the_stream_reading_tells_an_error_that_rounds_with_the_reference(
        monkeypatch):
    """`compare`'s `stream` sums on hand-made logits: an error that knows
    nothing of what a bfloat16 stream does to the reference carries none
    of it; one that shares a tenth of it, or a reference that has the
    stream's rounding itself, does not."""
    ref = evabyte_reference
    rs = np.random.default_rng(0)
    sound = rs.normal(size=(64, 67)).astype(np.float32)
    move = 0.01 * rs.normal(size=sound.shape).astype(np.float32)

    def forward(get, tokens, config, *, rows, spoil=None, **kw):
        logits = sound + move if spoil == "bf16_residual" else sound
        return ref.Forward(logits, None, None, None, None)

    monkeypatch.setattr(ref, "forward", forward)

    def reading(shared, spoil=None):
        mine = (sound + shared * move
                + 1.25 * 0.01 * rs.normal(size=sound.shape))
        got = ref.compare(None, [1] * 64, {}, dict(enumerate(mine)),
                          spoil=spoil)
        return got["stream"] and ref.stream_reading([got["stream"]])

    share, ratio = reading(0.0)
    assert abs(share) < 0.04 and ratio == pytest.approx(1.25, rel=0.05)
    assert reading(0.12)[0] > ref.STREAM_SHARE > abs(share)
    # the reference spoiled: the sound program's error has all of the move
    share, ratio = reading(0.0, "bf16_residual")
    assert share == pytest.approx(1.0, abs=0.05)
    assert ratio == pytest.approx(1.6, rel=0.05)
    assert reading(0.0, "no_mu_k") is None
    # a run's comparisons pool their sums, logit for logit
    assert ref.stream_reading([(9.0, 1.0, 0.5), (7.0, 3.0, 0.5)]) == (0.25, 2)


def test_traced_bytedocs_run_reads_what_it_can(tiny, capsys, monkeypatch):
    """--trace 1 with the trace steered to the recorded GPT-2 one (the CPU
    has no device plane): the job compiles the decode step's text for the
    scoped instructions; the readers find no step in that trace and leave
    the device metrics out; the counters' metrics are there."""
    import jax

    with open(os.path.join(HERE, "recorded_trace.textproto")) as f:
        recorded = trace.read(
            jax.profiler.ProfileData.from_text_proto(f.read()))
    monkeypatch.setattr(trace, "read_file", lambda path: recorded)
    seen = {}
    scoped = evabyte_events.scoped_instructions
    monkeypatch.setattr(
        evabyte_events, "scoped_instructions",
        lambda text: seen.setdefault("pairs", scoped(text)))
    assert run.main(["--workload", "tiny-bytedocs", "--seed", "1",
                     "--seconds", "0.5", "--trace", "1"], tiny) == 0
    out = capsys.readouterr().out
    line = result_line(out)
    assert line["correct"] is True, out
    assert {"prefix_hit_pct.serve", "engine_iter_ms", "chunk_step_ms.serve",
            "kv_bytes_a_token.serve", "eva_summary_rows_pct.serve",
            "ffcompile_s", "xla_compile_s"} <= set(line["metrics"])
    assert not {"eva_attend_ms.serve", "eva_attend_roofline_pct.serve",
                "eva_summarise_ms.serve"} & set(line["metrics"])
    assert line["metrics"]["prefix_hit_pct.serve"]["value"] > 50
    assert 0 < line["metrics"]["eva_summary_rows_pct.serve"]["value"] < 100
    # a held token costs its summaries (a row every 4 tokens of the exact
    # row's 2 layers x 2 x 64 x 4 B = 1,024 B: 256 B) and the exact rows of
    # the window blocks held beside the global ones (a window is 2 blocks
    # here, so their share swings with what the window leaves cached)
    assert 256 < line["metrics"]["kv_bytes_a_token.serve"]["value"] < 1280
    assert {"eva.qkv", "eva.attend", "eva.summarise",
            "eva.out"} <= {s for _, s in seen["pairs"]}


HLO = '''
  %fusion.1 = bf16[16,4096]{1,0} fusion(%p), kind=kOutput, calls=%f.1, metadata={op_name="jit(decode_step)/l0_attn/eva.qkv/dot_general"}
  %custom-call.2 = bf16[16,1,4096]{2,1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_step)/l0_attn/eva.attend/flash_attention_paged_decode_lse"}
  %fusion.3 = bf16[16,4096]{1,0} fusion(%z), kind=kLoop, calls=%f.3, metadata={op_name="jit(decode_step)/l0_attn/eva.attend/mul"}
  %fusion.4 = bf16[16,16,4096]{2,1,0} fusion(%z), kind=kOutput, calls=%f.4, metadata={op_name="jit(decode_step)/l0_attn/eva.summarise/gather"}
  ROOT %fusion.5 = f32[16,320]{1,0} fusion(%z), kind=kOutput, calls=%f.5, metadata={op_name="jit(decode_step)/lm_head/dot_general"}
'''


def hand_made_run(pairs, steps, counters=None):
    ms = 1_000_000
    ops = [("%fusion.1 = bf16[16,4096] fusion(%p)", 0, 1 * ms),
           ("%custom-call.2 = bf16[16,1,4096] custom-call(%p)", 1 * ms, 4 * ms),
           ("%fusion.3 = bf16[16,4096] fusion(%z)", 4 * ms, 5 * ms),
           ("%fusion.4 = bf16[16,16,4096] fusion(%z)", 5 * ms, 7 * ms),
           ("%fusion.5 = f32[16,320] fusion(%z)", 9 * ms, 10 * ms),
           # the second step
           ("%custom-call.2 = bf16[16,1,4096] custom-call(%p)", 20 * ms,
            24 * ms),
           # a chunk step's events: another step's interval, left out
           ("%custom-call.2 = bf16[16,1,4096] custom-call(%p)", 40 * ms,
            49 * ms)]
    r = types.SimpleNamespace(
        result={"counters": {"evabyte_instructions": pairs,
                             **(counters or {})}},
        config=harness.load_json("configs", CONFIG + ".json"),
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


ROWS = dict(eva_exact_rows=12000, eva_summary_rows=8000, kv_itemsize=2)
STEPS = [a_step(1, "decode", 0, 10, **ROWS),
         a_step(2, "decode", 20, 30, **ROWS), a_step(3, "chunk", 40, 50)]


def test_the_new_readers_on_hand_made_events():
    """Two pure-decode steps and a chunk step: the readers take the events
    inside the device's own intervals of the decode steps, by scope, and
    hold the core to the bytes of the rows the spans count."""
    pairs = evabyte_events.scoped_instructions(HLO)
    assert pairs == [["fusion.1", "eva.qkv"],
                     ["custom-call.2", "eva.attend"],
                     ["fusion.3", "eva.attend"],
                     ["fusion.4", "eva.summarise"]]
    r = hand_made_run(pairs, STEPS, dict(eva_exact_rows=600,
                                         eva_summary_rows=400))
    read = lambda name: harness.load_reader(name).read(r)  # noqa: E731
    assert read("eva_attend_ms.serve") == pytest.approx((3 + 1 + 4) / 2)
    assert read("eva_summarise_ms.serve") == pytest.approx(1.0)
    # by hand: 2 steps x 20,000 rows x 131,072 B = 5,242,880,000 B at 8e11
    # B/s = 6.5536 ms, over 8 ms of eva.attend
    assert read("eva_attend_roofline_pct.serve") == pytest.approx(
        100 * 5_242_880_000 / 8.0e11 / 8e-3)
    assert read("eva_attend_roofline_pct.serve") < 100
    assert read("eva_summary_rows_pct.serve") == pytest.approx(40.0)


def test_the_new_readers_find_nothing_on_a_parent_or_a_bad_join():
    pairs = evabyte_events.scoped_instructions(HLO)
    no_pairs = hand_made_run(None, STEPS)
    unjoined = hand_made_run(pairs, STEPS)
    unjoined.device_steps.dispatched = 4    # one step was not joined
    nothing = hand_made_run(pairs, STEPS)
    nothing.device_steps = None             # a program without `step` ids
    for r in (no_pairs, unjoined, nothing):
        for name in NEW:
            assert harness.load_reader(name).read(r) is None, name
    # a program whose spans lack the counts (a parent) reads the times and
    # no share
    bare = [a_step(1, "decode", 0, 10), a_step(2, "decode", 20, 30)]
    no_counts = hand_made_run(pairs, bare)
    assert harness.load_reader(NEW[0]).read(no_counts) == pytest.approx(4.0)
    assert harness.load_reader(NEW[1]).read(no_counts) is None
    assert harness.load_reader(NEW[3]).read(no_counts) is None
