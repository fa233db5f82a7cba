"""Pipelined execution engine tests (engine/): fused multi-step dispatch,
async input prefetch, chunk-boundary resilience, deferred health sync.

The headline property: `fit(..., pipeline_steps=N)` is BIT-IDENTICAL to
the eager loop — same losses, params, RNG stream, and step counters over
multiple shuffled epochs — while dispatching the epoch in ceil(B/N) fused
scans instead of B per-step calls, and resuming across kills to the same
trajectory.
"""

import os
import sys
import threading

import numpy as np
import pytest

pytestmark = pytest.mark.quick

DP8 = (8, 1, 1, 1)


def _mlp(batch=8, mesh=DP8, seed=0, argv=()):
    sys.argv = ["test", *argv]
    from flexflow_tpu import (
        ActiMode, FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
    )

    config = FFConfig()
    config.mesh_axis_sizes = mesh
    config.batch_size = batch
    config.seed = seed
    ff = FFModel(config)
    x = ff.create_tensor((batch, 16), name="x")
    t = ff.dense(x, 32, ActiMode.AC_MODE_RELU, name="fc1")
    t = ff.dense(t, 4, name="fc2")
    t = ff.softmax(t, name="sm")
    ff.compile(optimizer=SGDOptimizer(lr=0.05),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.METRICS_ACCURACY])
    return ff


def _data(n=64, d=16, k=4, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, d).astype(np.float32)
    y = rs.randint(0, k, (n, 1)).astype(np.int32)
    return x, y


class _StepSpy:
    """Diagnostics rule that records every per-step record it sees —
    the loss stream both loops feed the health engine."""

    name = "step_spy"

    def __init__(self):
        self.records = []

    def check(self, rec):
        self.records.append((int(rec["step"]), rec.get("loss")))
        return None


def _weights(ff):
    import jax

    return {
        "fc1": np.asarray(jax.device_get(ff.get_weight("fc1", "kernel"))),
        "fc2": np.asarray(jax.device_get(ff.get_weight("fc2", "kernel"))),
    }


def _no_prefetch_threads():
    return not [t for t in threading.enumerate()
                if t.name.startswith("ff-prefetch") and t.is_alive()]


# ===================================================================
# chunk planning + chunk-aware checkpoint policy
# ===================================================================

def test_plan_chunks():
    from flexflow_tpu.engine import plan_chunks

    assert plan_chunks(0, 8, 4) == [(0, 4), (4, 4)]
    assert plan_chunks(0, 8, 3) == [(0, 3), (3, 3), (6, 2)]  # tail chunk
    assert plan_chunks(5, 8, 4) == [(5, 3)]  # resume mid-epoch
    assert plan_chunks(8, 8, 4) == []  # nothing left
    assert plan_chunks(0, 1, 64) == [(0, 1)]
    with pytest.raises(ValueError):
        plan_chunks(0, 8, 0)


def test_checkpoint_policy_should_save_range():
    from flexflow_tpu.resilience import CheckpointPolicy

    p = CheckpointPolicy(every_n_steps=3)
    # chunk 5..8 contains step 6 — must save even though 8 % 3 != 0
    assert p.should_save_range(4, 8)
    assert p.should_save_range(0, 4)  # contains 3
    assert not p.should_save_range(3, 5)  # 4, 5: no multiple of 3
    assert not p.should_save_range(4, 4)  # empty range
    assert not CheckpointPolicy().should_save_range(0, 100)  # policy off


# ===================================================================
# prefetcher lifecycle
# ===================================================================

def test_prefetcher_delivers_in_order_and_exhausts():
    from flexflow_tpu.engine import ChunkPrefetcher, PrefetchExhausted

    pf = ChunkPrefetcher(lambda c: c * 10, [1, 2, 3], depth=2)
    assert [pf.get(), pf.get(), pf.get()] == [10, 20, 30]
    with pytest.raises(PrefetchExhausted):
        pf.get(timeout=5)
    pf.shutdown()
    assert not pf.alive


def test_prefetcher_staging_error_propagates_to_consumer():
    from flexflow_tpu.engine import ChunkPrefetcher

    pf = ChunkPrefetcher(lambda c: 1 // 0, [1, 2], depth=1)
    with pytest.raises(ZeroDivisionError):
        pf.get(timeout=5)
    pf.shutdown()
    assert not pf.alive


def test_prefetcher_shutdown_unblocks_worker_on_full_queue():
    from flexflow_tpu.engine import ChunkPrefetcher

    # depth=1 and an unconsumed backlog: the worker blocks on put();
    # shutdown must still leave the thread dead (no leak)
    pf = ChunkPrefetcher(lambda c: c, list(range(50)), depth=1)
    assert pf.get(timeout=5) == 0
    pf.shutdown()
    assert not pf.alive


# ===================================================================
# equivalence: pipelined fit == eager fit, bit for bit
# ===================================================================

def _fit_with_spy(tmpdir, pipeline_steps, epochs=2, n=64):
    import jax

    x, y = _data(n)
    ff = _mlp()
    spy = _StepSpy()
    ff.enable_diagnostics(str(tmpdir), rules=[spy])
    ff.fit(x, y, epochs=epochs, batch_size=8, shuffle=True,
           pipeline_steps=pipeline_steps)
    return {
        "losses": [l for _, l in spy.records],
        "steps": [s for s, _ in spy.records],
        "weights": _weights(ff),
        "rng": np.asarray(jax.device_get(jax.random.key_data(ff._rng))),
        "step": int(np.asarray(jax.device_get(ff._step))),
        "counters": {k: np.asarray(v) for k, v in
                     jax.device_get(ff._counters).items()},
    }


@pytest.mark.parametrize("pipeline_steps", [4, 3],
                         ids=["even-chunks", "ragged-tail"])
def test_pipelined_fit_bit_identical_to_eager(tmp_path, pipeline_steps):
    """THE equivalence gate: 2 shuffled epochs, same seed — losses,
    params, RNG stream, step counters, and metric counters all match the
    eager loop bit-exactly (pipeline_steps=3 exercises the shorter tail
    chunk: 8 batches/epoch → chunks of 3+3+2)."""
    eager = _fit_with_spy(tmp_path / "eager", 1)
    piped = _fit_with_spy(tmp_path / "piped", pipeline_steps)

    assert eager["steps"] == piped["steps"] == list(range(1, 17))
    assert eager["losses"] == piped["losses"]  # bit-exact floats
    assert eager["step"] == piped["step"] == 16
    np.testing.assert_array_equal(eager["rng"], piped["rng"])
    for k in eager["weights"]:
        np.testing.assert_array_equal(
            eager["weights"][k], piped["weights"][k],
            err_msg=f"weight {k} diverged")
    for k in eager["counters"]:
        np.testing.assert_array_equal(
            eager["counters"][k], piped["counters"][k],
            err_msg=f"counter {k} diverged")


def test_pipelined_telemetry_artifacts_schema_valid(tmp_path):
    """Pipelined mode must keep every observability consumer working:
    per-step metrics records (full time split), step/data_wait/chunk
    trace spans, checkpoint records, and a doctor verdict of healthy."""
    import json

    from flexflow_tpu.diagnostics.doctor import diagnose
    from flexflow_tpu.telemetry import read_jsonl

    tdir = tmp_path / "t"
    x, y = _data(64)
    ff = _mlp(argv=["--telemetry-dir", str(tdir),
                    "--checkpoint-dir", str(tmp_path / "ck"),
                    "--checkpoint-every", "4",
                    "--pipeline-steps", "4"])
    ff.enable_telemetry(str(tdir))
    ff.fit(x, y, epochs=1, batch_size=8, shuffle=True)

    recs = read_jsonl(os.path.join(str(tdir), "metrics.jsonl"))
    steps = [r for r in recs if r["kind"] == "step"]
    assert [r["step"] for r in steps] == list(range(1, 9))
    for s in steps:
        for f in ("step_time_s", "data_wait_s", "save_latency_s",
                  "device_time_s", "ema_step_time_s"):
            assert f in s, f"step record missing {f}"
    assert [r for r in recs if r["kind"] == "checkpoint"], \
        "chunk-boundary saves must produce checkpoint records"
    summ = [r for r in recs if r["kind"] == "summary"][-1]
    assert summ["steps"] == 8 and summ["examples_per_sec"] > 0

    with open(os.path.join(str(tdir), "trace.json")) as f:
        names = {e["name"] for e in json.load(f)["traceEvents"]}
    for required in ("step", "data_wait", "chunk", "prefetch.stage"):
        assert required in names, f"trace missing {required!r}"

    d = diagnose(str(tdir))
    assert d["steps"] == 8
    assert d["checkpoints"]["count"] >= 1


# ===================================================================
# resilience at chunk boundaries
# ===================================================================

def test_pipelined_kill_resume_bit_identical(tmp_path):
    """Mid-chunk injected death → auto-resume lands on a chunk-edge
    cursor and the resumed pipelined run reproduces the uninterrupted
    EAGER run bit-exactly (the equivalence and the resume proven in one
    trajectory)."""
    import jax

    from flexflow_tpu.resilience import (
        FaultInjector, SimulatedPreemption, latest_checkpoint,
        load_checkpoint)

    x, y = _data(64)  # 8 batches/epoch
    root = str(tmp_path / "ck")

    ref = _mlp()
    ref.fit(x, y, epochs=2, batch_size=8, shuffle=True)  # eager, 16 steps
    ref_w = _weights(ref)

    # killed pipelined run: chunks of 4, checkpoint cadence 3 (hits mid-
    # chunk — the boundary save logic must still fire), die at step 6
    ff1 = _mlp(argv=["--checkpoint-dir", root, "--checkpoint-every", "3",
                     "--pipeline-steps", "4"])
    fault = FaultInjector(kill_after_step=6)
    ff1.set_fault_hook(fault)
    with pytest.raises(SimulatedPreemption):
        ff1.fit(x, y, epochs=2, batch_size=8, shuffle=True)
    assert fault.fired
    assert _no_prefetch_threads(), "prefetch thread leaked across the kill"
    del ff1

    last = latest_checkpoint(root)
    assert last is not None
    _, manifest = load_checkpoint(last)
    cur = manifest["extras"]["cursor"]
    assert cur["batch"] % 4 == 0, f"cursor {cur} not on a chunk edge"

    ff2 = _mlp(argv=["--checkpoint-dir", root, "--auto-resume",
                     "--pipeline-steps", "4"])
    ff2.fit(x, y, epochs=2, batch_size=8, shuffle=True)
    assert int(np.asarray(jax.device_get(ff2._step))) == 16
    got = _weights(ff2)
    for k in ref_w:
        np.testing.assert_array_equal(
            got[k], ref_w[k],
            err_msg=f"weight {k} diverged after kill/resume")


def test_pipelined_sigterm_drains_at_chunk_boundary(tmp_path):
    """A preemption notice mid-chunk lets the running chunk finish, then
    finalizes with one synchronous snapshot at the NEXT chunk edge — the
    cursor rounds to the boundary and fit returns early."""
    import jax

    from flexflow_tpu.resilience import latest_checkpoint, load_checkpoint

    x, y = _data(128)  # 16 batches/epoch → chunks of 4
    root = str(tmp_path / "ck")
    ff = _mlp(argv=["--checkpoint-dir", root, "--pipeline-steps", "4"])

    _handler_holder = [None]

    def notice(step):
        if step == 2:  # delivered during chunk 1's boundary processing
            _handler_holder[0].request()

    from flexflow_tpu.resilience import policy as pol

    orig_enter = pol.PreemptionHandler.__enter__

    def capture_enter(self):
        _handler_holder[0] = self
        return orig_enter(self)

    pol.PreemptionHandler.__enter__ = capture_enter
    try:
        ff.set_fault_hook(notice)
        ff.fit(x, y, epochs=2, batch_size=8, shuffle=True)  # returns early
    finally:
        pol.PreemptionHandler.__enter__ = orig_enter

    # notice landed after chunk 1 (steps 1-4); chunk 2 (5-8) runs, then
    # the boundary drains + final-saves: stopped at step 8, cursor batch 8
    assert int(np.asarray(jax.device_get(ff._step))) == 8
    last = latest_checkpoint(root)
    assert last is not None and last.endswith("step_00000008")
    _, manifest = load_checkpoint(last)
    assert manifest["extras"]["cursor"] == {"epoch": 0, "batch": 8}
    assert _no_prefetch_threads()


def test_pipelined_health_abort_shuts_prefetcher_down(tmp_path):
    """An abort-listed rule firing mid-chunk stops fit with HealthAbort
    and the prefetch thread is joined — no leak even though the epoch had
    chunks still staged/queued."""
    from flexflow_tpu.diagnostics import HealthAbort
    from flexflow_tpu.diagnostics.health import Alert, Rule

    class BoomRule(Rule):
        name = "boom"

        def _check(self, rec):
            if rec["step"] >= 3:
                return Alert(rule=self.name, level="warning",
                             step=int(rec["step"]), message="boom")
            return None

    x, y = _data(128)  # plenty of chunks left to strand in the queue
    ff = _mlp()
    ff.enable_diagnostics(str(tmp_path / "t"), rules=[BoomRule()],
                          abort_on=("boom",))
    with pytest.raises(HealthAbort):
        ff.fit(x, y, epochs=2, batch_size=8, shuffle=True,
               pipeline_steps=4)
    assert _no_prefetch_threads(), "prefetch thread leaked after HealthAbort"


# ===================================================================
# satellites: dataloader spec cache, health sampling cadence
# ===================================================================

def test_dataloader_caches_partition_spec_lookup():
    """next_batch_sharded resolved the input's spec by scanning
    graph.sources() EVERY batch; it must now resolve once and reuse."""
    ff = _mlp()
    data = np.random.RandomState(0).randn(32, 16).astype(np.float32)
    loader = ff.create_data_loader(ff._input_tensors[0], data)

    calls = []
    orig = ff.graph.sources

    def counting_sources():
        calls.append(1)
        return orig()

    ff.graph.sources = counting_sources
    try:
        b1 = loader.next_batch_sharded()
        b2 = loader.next_batch_sharded()
    finally:
        ff.graph.sources = orig
    assert len(calls) == 1, f"sources() scanned {len(calls)}× for 2 batches"
    np.testing.assert_array_equal(np.asarray(b1), data[:8])
    np.testing.assert_array_equal(np.asarray(b2), data[8:16])
    assert b1.sharding.spec == ff.graph.sources()[0].outputs[0].partition_spec()


def test_health_sample_every_thins_loss_fetch(tmp_path):
    """--health-sample-every 3: the eager loop fetches the loss (a full
    device drain) only on steps 3 and 6, and the rules see ONE record
    per 3-step window carrying the window AVERAGE — dispatch-only
    timings from the unsynced steps in between never reach the
    spike/stall/drift baselines raw."""
    x, y = _data(64)
    ff = _mlp(argv=["--health-sample-every", "3"])
    spy = _StepSpy()
    ff.enable_diagnostics(str(tmp_path / "t"), rules=[spy])
    ff.fit(x, y, epochs=1, batch_size=8, shuffle=True)  # 8 steps
    assert [s for s, _ in spy.records] == [3, 6]
    assert all(l is not None for _, l in spy.records)


def test_health_sample_every_default_keeps_per_step_records(tmp_path):
    """K=1 (default) reduces to the old behavior exactly: one record per
    step, every one carrying the loss."""
    x, y = _data(64)
    ff = _mlp()
    spy = _StepSpy()
    ff.enable_diagnostics(str(tmp_path / "t"), rules=[spy])
    ff.fit(x, y, epochs=1, batch_size=8, shuffle=True)
    assert [s for s, _ in spy.records] == list(range(1, 9))
    assert all(l is not None for _, l in spy.records)


# ===================================================================
# one compilation per step program: what the steps hand back
# ===================================================================

def test_reset_metrics_does_not_recompile_the_train_step():
    """reset_metrics places the fresh counters as compile placed them.
    Unplaced counters would give the next step a second argument
    signature — a second compilation of the whole train step."""
    ff = _mlp()
    x, y = _data()
    fit = dict(epochs=1, batch_size=8, shuffle=False, verbose=False)
    ff.fit(x, y, **fit)
    step = ff.executor._train_step
    compiled = step._cache_size()
    ff.reset_metrics()
    ff.fit(x, y, **fit)
    assert step._cache_size() == compiled


def test_train_step_returns_weights_in_their_planned_placement():
    """A searched plan can shard an op's output where its weight is
    replicated (a LayerNorm under a feature-sharded activation). Left to
    GSPMD the updated weight comes back in its gradient's layout, and the
    second step compiles again for arguments the first was not compiled
    for; the step pins its outputs to the at-rest placement instead."""
    import jax
    from jax.sharding import PartitionSpec

    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models import TransformerLMConfig, build_transformer_lm
    from flexflow_tpu.parallel.strategies import Strategy

    sys.argv = ["test", "--mesh", "2,2,1,1"]
    config = FFConfig()
    config.batch_size = 4
    ff = FFModel(config)
    cfg = TransformerLMConfig(vocab_size=64, hidden_size=32, num_heads=2,
                              num_layers=1, sequence_length=8,
                              attention_impl="xla")
    build_transformer_lm(ff, cfg, batch_size=4)
    plan = Strategy()
    plan.set_output("l0_ln2", 0, (("data",), (), ("model",)))
    # a trailing None places like its trimmed form but does not compare
    # equal to it — and a step's outputs come back trimmed
    plan.set_weight("l0_ffn2", "kernel", PartitionSpec("model", None))
    ff.set_strategy(plan)
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
    rs = np.random.RandomState(0)
    x = {"tokens": rs.randint(0, 64, (8, 8)).astype(np.int32),
         "positions": np.tile(np.arange(8, dtype=np.int32), (8, 1))}
    y = rs.randint(0, 64, (8, 8, 1)).astype(np.int32)
    placed = jax.tree.map(lambda leaf: leaf.sharding, ff._params)
    ff.fit(x, y, epochs=1, batch_size=4, shuffle=False, verbose=False)
    assert ff.executor._train_step._cache_size() == 1
    assert jax.tree.map(lambda leaf: leaf.sharding, ff._params) == placed
    assert ff._params["l0_ln2"]["scale"].sharding.spec == PartitionSpec()
    assert (ff._params["l0_ffn2"]["kernel"].sharding.spec
            == PartitionSpec("model"))
