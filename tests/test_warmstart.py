"""Warm-start subsystem tests (warmstart/, docs/performance.md).

The acceptance surface of the persistent plan/calibration/executable
caches: a second compile against a shared `--warmstart-dir` must hit the
plan cache with ZERO search evaluations and a bit-identical strategy;
any fingerprint-component change must force a re-search; corrupt cache
entries must fall back cleanly (and self-repair); `--auto-resume` must
restore the plan from the checkpoint manifest without searching; and the
`Strategy.validate` gate must reject stale plans loudly for
`--import-strategy` while warm start treats the same failure as a miss.
"""

import json
import os
import sys

import numpy as np
import pytest

SEARCH_ARGV = ["--mesh", "2,4,1,1", "--budget", "6",
               "--enable-parameter-parallel"]


def _build(argv, hidden=256, batch=32, in_dim=64):
    """A small MLP with EXPLICIT layer names: default names embed the
    process-global layer guid, so two models built in one process would
    never share a fingerprint (separate processes — the real warm-start
    scenario — get deterministic defaults)."""
    sys.argv = ["test"] + list(argv)
    from flexflow_tpu import (
        ActiMode, FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
    )

    config = FFConfig()
    config.batch_size = batch
    ff = FFModel(config)
    x = ff.create_tensor((batch, in_dim))
    t = ff.dense(x, hidden, ActiMode.AC_MODE_RELU, name="ws_fc1")
    t = ff.dense(t, hidden, ActiMode.AC_MODE_RELU, name="ws_fc2")
    t = ff.dense(t, 10, name="ws_head")
    ff.compile(optimizer=SGDOptimizer(lr=0.05),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.METRICS_ACCURACY])
    return ff


def _strategy_json(ff) -> str:
    from flexflow_tpu.parallel.strategies import Strategy

    return json.dumps(Strategy(ff._strategy or {}).to_json(),
                      sort_keys=True)


class _EvalSpy:
    """Counts UnitySearch.evaluate calls AND joint_graph_optimize entries
    — the same hook test_strategy_io.py uses for the import path, plus
    the acceptance criterion's 0-evaluations check."""

    def __enter__(self):
        import flexflow_tpu.search.joint as joint
        import flexflow_tpu.search.unity as unity

        self.evals = 0
        self.searches = 0
        self._unity = unity
        self._joint = joint
        self._orig_eval = unity.UnitySearch.evaluate
        self._orig_opt = joint.joint_graph_optimize
        spy = self

        def eval_spy(us, *a, **kw):
            spy.evals += 1
            return spy._orig_eval(us, *a, **kw)

        def opt_spy(*a, **kw):
            spy.searches += 1
            return spy._orig_opt(*a, **kw)

        unity.UnitySearch.evaluate = eval_spy
        joint.joint_graph_optimize = opt_spy
        return self

    def __exit__(self, *exc):
        self._unity.UnitySearch.evaluate = self._orig_eval
        self._joint.joint_graph_optimize = self._orig_opt
        return False


def test_warm_compile_hits_plan_cache_zero_evals(tmp_path):
    """Second compile with a shared --warmstart-dir: plan_source=cache,
    0 evaluate() calls, 0 joint_graph_optimize calls, and the strategy is
    bit-identical to the cold run's."""
    ws = str(tmp_path / "ws")
    argv = SEARCH_ARGV + ["--warmstart-dir", ws]
    ff1 = _build(argv)
    assert ff1._plan_source == "search"
    assert os.path.isdir(os.path.join(ws, "plans"))

    with _EvalSpy() as spy:
        ff2 = _build(argv)
    assert spy.searches == 0, "plan cache hit must not re-search"
    assert spy.evals == 0, "plan cache hit must cost 0 evaluations"
    assert ff2._plan_source == "cache"
    assert _strategy_json(ff2) == _strategy_json(ff1)

    # the replayed plan still trains
    rs = np.random.RandomState(0)
    y = rs.randint(0, 10, 64)
    xs = rs.randn(64, 64).astype(np.float32)
    ff2.fit(xs, y.reshape(-1, 1).astype(np.int32), epochs=1)


def test_fingerprint_invalidation_forces_research(tmp_path):
    """Any fingerprint component change → miss: hidden size (graph),
    mesh shape, and a search flag each force a fresh search."""
    ws = str(tmp_path / "ws")
    argv = SEARCH_ARGV + ["--warmstart-dir", ws]
    _build(argv)  # populate the cache

    changed = [
        dict(argv=argv, hidden=128),                       # graph changed
        dict(argv=["--mesh", "4,2,1,1"] + argv[2:]),       # mesh changed
        dict(argv=[a if a != "6" else "4" for a in argv]),  # budget changed
    ]
    for kw in changed:
        with _EvalSpy() as spy:
            ff = _build(**kw)
        assert spy.searches >= 1, kw
        assert ff._plan_source == "search", kw

    # and the unchanged config still hits afterwards (misses were stored
    # under their own addresses, not over the original entry)
    with _EvalSpy() as spy:
        ff = _build(argv)
    assert spy.evals == 0 and ff._plan_source == "cache"


def test_corrupt_plan_entry_falls_back_and_repairs(tmp_path):
    """A truncated cache entry reads as a miss (warn, search fresh) and
    the entry is rewritten; a junk-JSON entry likewise."""
    import glob

    ws = str(tmp_path / "ws")
    argv = SEARCH_ARGV + ["--warmstart-dir", ws]
    _build(argv)
    (plan_file,) = glob.glob(os.path.join(ws, "plans", "*.json"))

    with open(plan_file, "w") as f:
        f.write('{"version": 1, "fingerpr')  # torn write
    with _EvalSpy() as spy:
        ff = _build(argv)
    assert ff._plan_source == "search" and spy.searches >= 1

    # the miss re-stored the entry: next compile hits again
    entry = json.load(open(plan_file))
    assert entry["version"] == 1 and "strategy" in entry
    with _EvalSpy() as spy:
        ff = _build(argv)
    assert ff._plan_source == "cache" and spy.evals == 0

    # wrong-model entry (valid JSON, stale content) also falls back
    entry["strategy"] = {"version": 1,
                         "nodes": {"not_a_node": {
                             "outputs": {"0": [["data"], []]},
                             "weights": {}}}}
    with open(plan_file, "w") as f:
        json.dump(entry, f)
    ff = _build(argv)
    assert ff._plan_source == "search"


def test_auto_resume_restores_plan_from_manifest(tmp_path):
    """The checkpoint manifest records the plan + structural fingerprint;
    --auto-resume adopts it at compile with zero searches, then fit
    restores the weights as before."""
    cd = str(tmp_path / "ckpt")
    argv = SEARCH_ARGV + ["--checkpoint-dir", cd, "--checkpoint-every", "2"]
    ff1 = _build(argv)
    rs = np.random.RandomState(0)
    y = rs.randint(0, 10, 128)
    xs = rs.randn(128, 64).astype(np.float32)
    ff1.fit(xs, y.reshape(-1, 1).astype(np.int32), epochs=1)

    from flexflow_tpu.resilience.checkpointer import latest_checkpoint

    path = latest_checkpoint(cd)
    assert path is not None
    man = json.load(open(os.path.join(path, "manifest.json")))
    plan = man["extras"]["plan"]
    assert plan["structural_fingerprint"] == ff1._plan_fingerprint
    assert plan["plan_source"] == "search"

    with _EvalSpy() as spy:
        ff2 = _build(argv + ["--auto-resume"])
    assert spy.searches == 0 and spy.evals == 0
    assert ff2._plan_source == "checkpoint"
    assert _strategy_json(ff2) == _strategy_json(ff1)
    # weights restore + training continues from the cursor
    ff2.fit(xs, y.reshape(-1, 1).astype(np.int32), epochs=1)
    assert ff2._py_step() > 0


def test_auto_resume_plan_mismatch_searches_fresh(tmp_path):
    """A config change between the checkpointed run and the resume must
    NOT adopt the stale plan (structural fingerprint mismatch)."""
    cd = str(tmp_path / "ckpt")
    argv = SEARCH_ARGV + ["--checkpoint-dir", cd, "--checkpoint-every", "2"]
    ff1 = _build(argv)
    rs = np.random.RandomState(0)
    y = rs.randint(0, 10, 64)
    xs = rs.randn(64, 64).astype(np.float32)
    ff1.fit(xs, y.reshape(-1, 1).astype(np.int32), epochs=1)

    with _EvalSpy() as spy:
        ff2 = _build(argv + ["--auto-resume"], hidden=128)  # graph changed
    assert spy.searches >= 1
    assert ff2._plan_source == "search"


def test_calibration_db_persists_measurements(tmp_path):
    """Cold compile with --calibrate N persists the measurements; the
    warm compile loads them and measures ZERO ops (all cache hits), and
    the compile.calibrate stats record the split."""
    from flexflow_tpu.search.cost_model import CostModel

    ws = str(tmp_path / "ws")
    argv = SEARCH_ARGV + ["--warmstart-dir", ws, "--calibrate", "1"]
    ff1 = _build(argv)
    db_path = os.path.join(ws, "calibration.json")
    assert os.path.exists(db_path)
    db = json.load(open(db_path))
    (dev_entries,) = db["devices"].values()
    assert len(dev_entries) >= 1
    for fwd_bwd in dev_entries.values():
        assert fwd_bwd[0] > 0 and fwd_bwd[1] > 0

    measured = []
    orig = CostModel.calibrate

    def spy(self, node, fn, args):
        measured.append(node.name)
        return orig(self, node, fn, args)

    CostModel.calibrate = spy
    try:
        ff2 = _build(argv)
    finally:
        CostModel.calibrate = orig
    assert measured == [], "warm calibration must be all cache hits"
    assert ff2._plan_source == "cache"
    stats = ff2._warmstart._cost_model.calib_stats
    assert stats["measured"] == 0
    assert stats["cache_hits"] >= 1
    assert ff1._plan_fingerprint == ff2._plan_fingerprint


def test_strategy_validate_rejects_stale_plans():
    """The shared validator: unknown nodes, unknown weights, absent mesh
    axes, rank mismatches, and indivisible dims all fail with messages
    naming the problem; the node's real placement passes."""
    from jax.sharding import PartitionSpec as P

    from flexflow_tpu.parallel.strategies import Strategy

    ff = _build(["--mesh", "2,4,1,1", "--only-data-parallel"])
    g, mesh = ff.graph, ff.mesh

    ok = Strategy()
    ok.set_output("ws_fc1", 0, (("data",), ("model",)))
    ok.set_weight("ws_fc1", "kernel", P(None, "model"))
    ok.validate(g, mesh)  # no raise

    bad = Strategy()
    bad.set_output("phantom_node", 0, (("data",), ()))
    with pytest.raises(ValueError, match="phantom_node"):
        bad.validate(g, mesh)

    bad = Strategy()
    bad.set_output("ws_fc1", 0, (("nonexistent_axis",), ()))
    with pytest.raises(ValueError, match="nonexistent_axis"):
        bad.validate(g, mesh)

    bad = Strategy()
    bad.set_weight("ws_fc1", "no_such_weight", P("model"))
    with pytest.raises(ValueError, match="no_such_weight"):
        bad.validate(g, mesh)

    bad = Strategy()
    bad.set_output("ws_fc1", 0, (("data",),))  # rank 1 vs 2
    with pytest.raises(ValueError, match="dims"):
        bad.validate(g, mesh)

    bad = Strategy()
    # head output dim 10 is not divisible by model axis size 4
    bad.set_output("ws_head", 0, ((), ("model",)))
    with pytest.raises(ValueError, match="divisible"):
        bad.validate(g, mesh)

    bad = Strategy()
    # 3-entry spec on a 2-D kernel: would surface as an opaque sharding
    # error deep in the executor without the validator
    bad.set_weight("ws_fc1", "kernel", P("model", None, None))
    with pytest.raises(ValueError, match="3 dims"):
        bad.validate(g, mesh)


def test_import_strategy_validates_loudly(tmp_path):
    """--import-strategy with a plan naming nodes from another model must
    raise a clear error instead of silently applying nothing."""
    plan = tmp_path / "stale.json"
    plan.write_text(json.dumps({
        "version": 1,
        "nodes": {"some_other_models_layer": {
            "outputs": {"0": [["data"], []]}, "weights": {}}},
    }))
    with pytest.raises(ValueError, match="some_other_models_layer"):
        _build(["--mesh", "2,4,1,1", "--import-strategy", str(plan)])


def test_time_to_first_step_in_summary(tmp_path):
    """The fit summary reports time_to_first_step_s (compile start →
    first step completion) — the cold-vs-warm restart metric."""
    from flexflow_tpu.telemetry import read_jsonl

    tdir = str(tmp_path / "tel")
    ff = _build(["--mesh", "2,4,1,1", "--only-data-parallel",
                 "--telemetry-dir", tdir])
    rs = np.random.RandomState(0)
    y = rs.randint(0, 10, 64)
    xs = rs.randn(64, 64).astype(np.float32)
    ff.fit(xs, y.reshape(-1, 1).astype(np.int32), epochs=1)
    recs = read_jsonl(os.path.join(tdir, "metrics.jsonl"))
    (summary,) = [r for r in recs if r["kind"] == "summary"]
    assert summary["time_to_first_step_s"] > 0
    compile_recs = [r for r in recs if r["kind"] == "compile"]
    assert compile_recs and compile_recs[0]["plan_source"] == "default"
    # first step completes after compile ends, so ttfs > compile time
    assert (summary["time_to_first_step_s"]
            > compile_recs[0]["duration_s"] * 0.5)


def test_warmstart_telemetry_records_hit(tmp_path):
    """metrics.jsonl carries the warmstart event (miss on the cold
    compile, hit on the warm one) and the compile record's plan_source
    flips search → cache."""
    from flexflow_tpu.telemetry import read_jsonl

    ws = str(tmp_path / "ws")

    def run(tag):
        tdir = str(tmp_path / tag)
        ff = _build(SEARCH_ARGV + ["--warmstart-dir", ws,
                                   "--telemetry-dir", tdir])
        # compile-only telemetry still flushes through the compile hook
        return ff, read_jsonl(os.path.join(tdir, "metrics.jsonl"))

    _, cold = run("cold")
    _, warm = run("warm")
    (cold_ws,) = [r for r in cold if r["kind"] == "warmstart"]
    (warm_ws,) = [r for r in warm if r["kind"] == "warmstart"]
    assert cold_ws["plan"] == "miss"
    assert warm_ws["plan"] == "hit" and warm_ws["source"] == "cache"
    (cold_c,) = [r for r in cold if r["kind"] == "compile"]
    (warm_c,) = [r for r in warm if r["kind"] == "compile"]
    assert cold_c["plan_source"] == "search"
    assert warm_c["plan_source"] == "cache"


def test_warm_strategy_report_describes_adopted_plan(tmp_path):
    """With --diagnostics, the warm compile's strategy report must
    attribute the ADOPTED plan (mode=replayed, same per-op configs and
    predicted makespan as the cold run's searched report) — NOT the
    data-parallel fallback, which would arm the drift monitor with the
    wrong prediction and fire false advisories on every warm restart."""
    from flexflow_tpu import telemetry

    ws = str(tmp_path / "ws")

    def run(tag):
        tdir = str(tmp_path / tag)
        # --calibrate: the warm report must price the replayed plan with
        # the persisted measurements, not the bare roofline — the parity
        # assert below fails otherwise
        ff = _build(SEARCH_ARGV + ["--warmstart-dir", ws,
                                   "--telemetry-dir", tdir,
                                   "--diagnostics", "--calibrate", "1"])
        telemetry.deactivate()
        return ff, json.load(
            open(os.path.join(tdir, "strategy_report.json")))

    _, cold = run("cold")
    warm_ff, warm = run("warm")
    assert cold["mode"] == "searched" and cold["plan_source"] == "search"
    assert warm["mode"] == "replayed" and warm["plan_source"] == "cache"
    cold_cfg = {o["name"]: o["config"] for o in cold["ops"]}
    warm_cfg = {o["name"]: o["config"] for o in warm["ops"]}
    assert warm_cfg == cold_cfg
    assert warm["total_predicted_s"] == pytest.approx(
        cold["total_predicted_s"], rel=1e-9)
    # the reconstructed (UnitySearch, choice) is stashed so drift
    # recalibration stays reachable on warm runs (_search_result is None)
    assert warm_ff._search_result is None
    us, choice = warm_ff._replay_search
    t, _ = us.evaluate(choice)
    assert t == pytest.approx(warm["total_predicted_s"], rel=1e-9)


def test_executable_cache_populated(tmp_path, monkeypatch):
    """With no cache placed from outside, the warm-start dir accumulates
    executable entries during compile. The model dims are unique to this
    test: jax memoizes compilation per-process by HLO hash, so an
    already-compiled model would never reach the persistent-cache layer
    again."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    ws = str(tmp_path / "ws")
    ff = _build(["--mesh", "2,4,1,1", "--only-data-parallel",
                 "--warmstart-dir", ws], hidden=192, in_dim=48)
    cache_dir = os.path.join(ws, "xla_cache")
    assert ff._warmstart.executable_cache_dir == cache_dir
    assert os.path.isdir(cache_dir)
    assert len(os.listdir(cache_dir)) > 0


def test_executable_cache_placed_from_outside_is_not_overridden(
        tmp_path, monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set, the warm-start manager
    keeps its plan cache and calibration DB under --warmstart-dir and
    sets no compile-cache directory of its own."""
    import jax

    outside = str(tmp_path / "outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    before = jax.config.jax_compilation_cache_dir
    ws = str(tmp_path / "ws")
    ff = _build(["--mesh", "2,4,1,1", "--only-data-parallel",
                 "--warmstart-dir", ws], hidden=176, in_dim=40)
    assert ff._warmstart.executable_cache_dir == outside
    assert jax.config.jax_compilation_cache_dir == before
    assert not os.path.exists(os.path.join(ws, "xla_cache"))
