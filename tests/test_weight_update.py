"""Weight-update sharding (ZeRO / Xu et al. 2020) tests.

The acceptance bar: the sharded update is BIT-IDENTICAL to the replicated
baseline — same reduced gradient elements feed the same element-wise
update, each replica just owns a slice — over multi-epoch trajectories
(params, Adam slots, RNG, counters), through kill→auto-resume across an
update-mode toggle and a mesh change, while Unity's update-dimension
decision (choose_update_sharding) flips to the sharded plan exactly when
the config is memory-bound and stays replicated when overlap pricing is
off and memory fits.
"""

import sys

import numpy as np
import pytest

pytestmark = pytest.mark.quick

DP4 = (4, 1, 1, 1)
DP8 = (8, 1, 1, 1)
DP2_TP2 = (2, 2, 1, 1)


def _mlp(batch=8, mesh=DP4, seed=0, argv=(), opt="adam", depth=0):
    """2-dense MLP; `depth` adds hidden layers (fc_h*) — stage 3 only
    pays off past ~3 layers (two-layers-in-flight < whole model), so
    the stage-3 memory tests use a deeper stack."""
    sys.argv = ["test", *argv]
    from flexflow_tpu import (
        ActiMode, AdamOptimizer, FFConfig, FFModel, LossType, MetricsType,
        SGDOptimizer,
    )

    config = FFConfig()
    config.mesh_axis_sizes = mesh
    config.batch_size = batch
    config.seed = seed
    ff = FFModel(config)
    x = ff.create_tensor((batch, 16), name="x")
    t = ff.dense(x, 32, ActiMode.AC_MODE_RELU, name="fc1")
    for i in range(depth):
        t = ff.dense(t, 32, ActiMode.AC_MODE_RELU, name=f"fc_h{i}")
    t = ff.dense(t, 4, name="fc2")
    t = ff.softmax(t, name="sm")
    optimizer = (AdamOptimizer(alpha=0.01) if opt == "adam"
                 else SGDOptimizer(lr=0.05, momentum=0.9))
    ff.compile(optimizer=optimizer,
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.METRICS_ACCURACY])
    return ff


def _data(n=64, d=16, k=4, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, d).astype(np.float32)
    y = rs.randint(0, k, (n, 1)).astype(np.int32)
    return x, y


def _full_state(ff):
    """Every trajectory-defining leaf, fetched to host."""
    import jax

    return {
        "params": jax.tree_util.tree_map(
            lambda a: np.asarray(jax.device_get(a)), ff._params),
        "slots": jax.tree_util.tree_map(
            lambda a: np.asarray(jax.device_get(a)), ff._opt_slots),
        "counters": jax.tree_util.tree_map(
            lambda a: np.asarray(jax.device_get(a)), ff._counters),
        "step": np.asarray(jax.device_get(ff._step)),
        "rng": np.asarray(jax.random.key_data(ff._rng)),
    }


def _assert_bit_equal(a, b, what=""):
    import jax

    fa, _ = jax.tree_util.tree_flatten_with_path(a)
    fb, _ = jax.tree_util.tree_flatten_with_path(b)
    assert len(fa) == len(fb)
    for (pa, la), (_, lb) in zip(fa, fb):
        assert np.array_equal(np.asarray(la), np.asarray(lb)), (
            f"{what}{jax.tree_util.keystr(pa)} differs: "
            f"max|Δ|={np.max(np.abs(np.asarray(la, np.float64) - np.asarray(lb, np.float64)))}")


# ===================================================================
# bit-exact trajectory parity
# ===================================================================

@pytest.mark.parametrize("opt", ["adam", "sgd_momentum"])
def test_sharded_update_bit_identical_trajectory(opt):
    """2 shuffled epochs under the forced-sharded update equal the
    replicated baseline bit-for-bit: params, optimizer slots (Adam m/v or
    SGD momentum), metric counters, step counter, RNG key."""
    x, y = _data(64)

    rep = _mlp(argv=["--no-weight-update-sharding"], opt=opt)
    rep.fit(x, y, epochs=2, batch_size=8, shuffle=True)

    sh = _mlp(argv=["--weight-update-sharding"], opt=opt)
    assert sh._update_sharding["enabled"] and sh._update_sharding["shards"] == 4
    assert sh.executor.update_specs, "no weight got an update sharding"
    sh.fit(x, y, epochs=2, batch_size=8, shuffle=True)

    assert not rep._update_sharding["enabled"]
    _assert_bit_equal(_full_state(rep), _full_state(sh))


def test_sharded_masters_and_slots_live_1_over_dp():
    """The at-rest layout really is ZeRO: fp32 masters and both Adam slots
    of every sharded weight are placed 1/dp along the update axis — each
    chip's addressable shard holds 1/4 of the bytes the replicated layout
    would — and the executor's decision record counts them."""
    ff = _mlp(argv=["--weight-update-sharding"])
    specs = ff.executor.update_specs
    assert ("fc1", "kernel") in specs and ("fc2", "kernel") in specs
    for (node, wname), (spec, shape) in specs.items():
        axes = [ax for entry in spec for ax in
                ((entry,) if isinstance(entry, str) else (entry or ()))]
        assert "data" in axes, (node, wname, spec)
    k = ff._params["fc1"]["kernel"]
    shard = k.addressable_shards[0].data
    assert shard.size * 4 == k.size, (shard.shape, k.shape)
    for slot_tree in ff._opt_slots.values():
        s = slot_tree["fc1"]["kernel"]
        assert s.addressable_shards[0].data.size * 4 == s.size
    upd = ff.executor.update_sharding
    assert upd["sharded_weights"] == len(specs) and upd["buckets"] >= 2


# ===================================================================
# kill → auto-resume across update modes and meshes
# ===================================================================

def test_kill_resume_toggled_update_mode_bit_exact(tmp_path):
    """Death mid-fit under the SHARDED update, auto-resume under the
    REPLICATED update on the same mesh: the final state is bit-equal to an
    uninterrupted replicated run — checkpoints hold full logical arrays,
    so the restoring compile re-places them under its own update mode."""
    from flexflow_tpu.resilience import FaultInjector, SimulatedPreemption

    x, y = _data(64)
    root = str(tmp_path / "ck")

    ref = _mlp(argv=["--no-weight-update-sharding"])
    ref.fit(x, y, epochs=2, batch_size=8, shuffle=True)

    ff1 = _mlp(argv=["--weight-update-sharding",
                     "--checkpoint-dir", root, "--checkpoint-every", "2"])
    fault = FaultInjector(kill_after_step=5)
    ff1.set_fault_hook(fault)
    with pytest.raises(SimulatedPreemption):
        ff1.fit(x, y, epochs=2, batch_size=8, shuffle=True)
    del ff1

    ff2 = _mlp(argv=["--no-weight-update-sharding",
                     "--checkpoint-dir", root, "--auto-resume"])
    ff2.fit(x, y, epochs=2, batch_size=8, shuffle=True)
    _assert_bit_equal(_full_state(ref), _full_state(ff2))


def test_kill_resume_across_dp_change_and_back(tmp_path):
    """The acceptance scenario: dp=4 sharded → (kill) → dp=2×tp=2
    replicated → (checkpoint) → back to dp=4 sharded. The trajectory
    continues across both reshard directions; the tp=2 leg changes matmul
    reduction order, so the cross-mesh comparison is the resilience
    suite's fp tolerance, not bit-equality."""
    import jax

    from flexflow_tpu.resilience import FaultInjector, SimulatedPreemption

    x, y = _data(64)
    root = str(tmp_path / "ck")

    ref = _mlp(mesh=DP4, argv=["--no-weight-update-sharding"])
    ref.fit(x, y, epochs=3, batch_size=8, shuffle=True)
    ref_state = _full_state(ref)

    # leg 1: dp=4, ZeRO-sharded update, dies at step 5 (last commit: 4)
    ff1 = _mlp(mesh=DP4, argv=["--weight-update-sharding",
                               "--checkpoint-dir", root,
                               "--checkpoint-every", "2"])
    ff1.set_fault_hook(FaultInjector(kill_after_step=5))
    with pytest.raises(SimulatedPreemption):
        ff1.fit(x, y, epochs=3, batch_size=8, shuffle=True)
    del ff1

    # leg 2: dp=2×tp=2, replicated update, finishes epoch 2 then "dies"
    # after its final save (manifest records the replicated update mode)
    ff2 = _mlp(mesh=DP2_TP2, argv=["--no-weight-update-sharding",
                                   "--checkpoint-dir", root,
                                   "--auto-resume"])
    ff2.fit(x, y, epochs=2, batch_size=8, shuffle=True)
    assert not ff2._update_sharding["enabled"]
    ff2._resilience.save(int(np.asarray(jax.device_get(ff2._step))),
                         cursor={"epoch": 2, "batch": 0}, blocking=True)
    mani = ff2._resilience.peek_latest()[1]
    assert mani["update_sharding"]["enabled"] is False
    assert mani["mesh_axes"]["model"] == 2
    del ff2

    # leg 3: back on dp=4 with the sharded update, finishes epoch 3
    ff3 = _mlp(mesh=DP4, argv=["--weight-update-sharding",
                               "--checkpoint-dir", root, "--auto-resume"])
    ff3.fit(x, y, epochs=3, batch_size=8, shuffle=True)
    assert ff3._update_sharding["enabled"]
    got = _full_state(ff3)
    assert np.array_equal(got["step"], ref_state["step"])
    for sec in ("params", "slots", "counters"):
        fa, _ = jax.tree_util.tree_flatten_with_path(ref_state[sec])
        fb, _ = jax.tree_util.tree_flatten_with_path(got[sec])
        for (pa, la), (_, lb) in zip(fa, fb):
            np.testing.assert_allclose(
                np.asarray(la), np.asarray(lb), rtol=2e-4, atol=1e-6,
                err_msg=f"{sec}{jax.tree_util.keystr(pa)} diverged across "
                        f"dp4-sharded→dp2tp2-replicated→dp4-sharded")


def test_checkpoint_manifest_records_update_sharding(tmp_path):
    """Manifests carry the saving run's update mode (shards, axes) so
    post-mortems and elastic resume can see how the writer ran."""
    import jax

    x, y = _data(32)
    root = str(tmp_path / "ck")
    ff = _mlp(argv=["--weight-update-sharding", "--checkpoint-dir", root])
    ff.fit(x, y, epochs=1, batch_size=8, shuffle=False)
    ff._resilience.save(int(np.asarray(jax.device_get(ff._step))),
                        cursor={"epoch": 1, "batch": 0}, blocking=True)
    _, extras = ff._resilience.peek_latest()
    upd = extras["update_sharding"]
    # bare --weight-update-sharding: forced on, stage priced (memory is
    # comfortable on the CI mesh, so the bare flag resolves to stage 2)
    assert upd == {"enabled": True, "stage": 2, "shards": 4,
                   "axes": ["data"]}


# ===================================================================
# the update-dimension search (choose_update_sharding) + cost model
# ===================================================================

def test_memory_pressure_flips_search_to_sharded():
    """Auto mode (no flag): with per-chip HBM capped below the replicated
    plan's footprint (-ll:fsize), Unity's update-dimension decision flips
    to the sharded update; the predicted sharded memory is genuinely
    smaller (the 1/dp masters+slots saving)."""
    ff = _mlp(argv=["-ll:fsize", "0.007"])  # ~7 KiB/chip: memory-bound
    dec = ff._update_sharding
    assert dec["enabled"] and dec["forced"] is None
    assert dec["reason"] == "memory_bound"
    p = dec["predicted"]
    assert p["sharded_mem_bytes"] < p["replicated_mem_bytes"]
    # the replicated plan is over the cap; the sharded one fits under it
    assert p["replicated_mem_bytes"] > p["hbm_cap_bytes"]
    assert p["sharded_mem_bytes"] <= p["hbm_cap_bytes"]
    # and the executor is actually running the sharded update
    assert ff.executor.update_specs


def test_replicated_wins_when_memory_fits_and_no_overlap():
    """Auto mode with overlap pricing off and memory comfortable: RS+AG
    moves the allreduce's exact ring bytes with extra hop latency and no
    channel to hide on, so the decision stays replicated."""
    ff = _mlp(argv=["--no-overlap-collectives"])
    dec = ff._update_sharding
    assert not dec["enabled"] and dec["forced"] is None
    assert dec["reason"] == "replicated_cheaper"
    assert not ff.executor.update_specs


def test_cost_model_prices_sharded_state_and_hops():
    """CostModel.op_cost under update_sharding: per-chip memory shrinks by
    the 1/shards masters+grad+slots term, update_shards/update_hops are
    populated, and the RS+AG sync moves the same ring bytes as the
    allreduce (machine-model identity all_reduce = RS + AG)."""
    from flexflow_tpu.search.cost_model import CostModel
    from flexflow_tpu.search.machine_model import machine_model_for_mesh
    from flexflow_tpu.search.substitution import _logical_assignment

    ff = _mlp(argv=["--no-weight-update-sharding"])
    node = next(n for n in ff.graph.topo_order()
                if n.name == "fc1" and n.weight_specs)
    cm = CostModel(machine_model_for_mesh(ff.mesh), opt_slots=2)

    def price():
        cm._cache.clear()
        return cm.op_cost(
            node, [_logical_assignment(pt) for pt in node.outputs],
            dict(node.weight_axes),
            [tuple(d.size for d in pt.shape.dims if not d.is_replica_dim)
             for pt in node.inputs],
            [_logical_assignment(pt) for pt in node.inputs])

    rep = price()
    cm.update_sharding = True
    sh = price()
    assert rep.update_shards == 1 and rep.update_hops == 0.0
    assert rep.update_sync_time == 0.0
    assert sh.update_shards == 4 and sh.update_hops > 0.0
    assert sh.update_hop_s > 0.0
    assert sh.memory < rep.memory
    # same ring bytes: the sharded RS+AG pair (update_sync_time — the
    # channel the evaluators may overlap) prices equal to the allreduce
    # it replaces, and no serial sync remains (every weight sharded here)
    assert sh.sync_time == 0.0
    assert sh.update_sync_time == pytest.approx(rep.sync_time, rel=1e-9)
    # the 1/dp saving is exactly masters+grad+slots going to 1/shards plus
    # one gathered compute copy, per trainable weight
    saved = sum(float(np.prod(ws.shape)) * 4 * ((2 + 2) * (1 - 1 / 4) - 1)
                for ws in node.weight_specs if ws.trainable)
    assert rep.memory - sh.memory == pytest.approx(saved, rel=1e-6)


# ===================================================================
# strategy report + telemetry surface
# ===================================================================

def test_strategy_report_surfaces_grad_sync_and_identity(tmp_path):
    """strategy_report.json under the sharded update: update_sharding /
    update_shards / grad_sync_s surfaced, the grad RS+AG priced on the
    overlappable channel (overlap_s covers it), and verify_report_total
    still reproduces total_predicted_s — the makespan identity extended
    to the grad-sync channel."""
    import json
    import os

    from flexflow_tpu.diagnostics.explain import verify_report_total

    tdir = str(tmp_path / "telemetry")
    x, y = _data(32)
    ff = _mlp(argv=["--weight-update-sharding", "--diagnostics",
                    "--telemetry-dir", tdir])
    ff.fit(x, y, epochs=1, batch_size=8, shuffle=False)
    ff.get_telemetry().close()

    with open(os.path.join(tdir, "strategy_report.json")) as f:
        report = json.load(f)
    assert report["update_sharding"] is True
    assert report["update_shards"] == 4
    assert report["grad_sync_s"] > 0.0
    synced = [o for o in report["ops"] if o["grad_sync_s"] > 0.0]
    assert synced, "no op carries grad_sync_s"
    for o in synced:
        # the sharded grad sync rides the overlappable channel
        assert o["overlap_s"] >= o["grad_sync_s"]
        assert o["sync_s"] == 0.0
    total = verify_report_total(report)
    pred = report["total_predicted_s"]
    assert abs(total - pred) <= 1e-9 + 1e-6 * abs(pred)


def test_weight_update_telemetry_events(tmp_path):
    """Compile emits the weight_update event (shards, buckets, bytes) and
    per-bucket grad_sync counters; the decision event records why."""
    import os

    from flexflow_tpu.telemetry import read_jsonl

    tdir = str(tmp_path / "telemetry")
    x, y = _data(32)
    ff = _mlp(argv=["--weight-update-sharding", "--telemetry-dir", tdir])
    ff.fit(x, y, epochs=1, batch_size=8, shuffle=False)
    ff.get_telemetry().close()

    recs = list(read_jsonl(os.path.join(tdir, "metrics.jsonl")))
    wu = [r for r in recs if r.get("kind") == "weight_update"]
    assert wu and wu[0]["shards"] == 4 and wu[0]["buckets"] >= 2
    assert wu[0]["bytes"] > 0
    dec = [r for r in recs if r.get("kind") == "weight_update_decision"]
    assert dec and dec[0]["enabled"] is True

    with open(os.path.join(tdir, "trace.json")) as f:
        raw = f.read()
    assert '"grad_sync"' in raw, "no grad_sync span/counter in the trace"


@pytest.mark.parametrize("dtype_flags,itemsize", [((), 4),
                                                  (("--dtype", "bf16"), 2)],
                         ids=["float32", "bf16"])
def test_param_gather_event_counts_the_compute_dtype(tmp_path, dtype_flags,
                                                     itemsize):
    """A stage-3 compile's param_gather event: the bytes a chip's gathers
    deliver a step in the dtype the wire carries (the compute dtype), the
    collective's kind, and one gather a step for every gathered weight."""
    import os

    from flexflow_tpu.telemetry import read_jsonl

    tdir = str(tmp_path / "telemetry")
    ff = _mlp(argv=["--weight-update-sharding=stage3", *dtype_flags,
                    "--telemetry-dir", tdir])
    ff.get_telemetry().close()
    (event,) = [r for r in read_jsonl(os.path.join(tdir, "metrics.jsonl"))
                if r.get("kind") == "param_gather"]
    specs = ff.executor.gather_specs
    assert specs and event["sharded_weights"] == len(specs)
    assert event["collective"] == "all-gather"
    assert event["gathers_per_step"] == len(specs)   # one update axis each
    assert event["bytes"] == itemsize * sum(
        int(np.prod(ff.executor.update_specs[key][1])) for key in specs)
    assert "overlap" not in event


# ===================================================================
# the explicit ring reduce-scatter (bench ablation substrate)
# ===================================================================

@pytest.mark.parametrize("overlap", [True, False],
                         ids=["overlapped", "serial"])
def test_ring_reduce_scatter_matches_reference(overlap):
    """ring_reduce_scatter (the double-buffered ppermute schedule the
    sharded grad sync lowers to, and bench.py's microbench subject)
    computes the exact reduce-scatter: chunk c of the output is the
    cross-shard sum of every shard's local chunk c."""
    import jax

    from flexflow_tpu.machine import MeshShape, build_mesh
    from flexflow_tpu.parallel.ops import ring_reduce_scatter

    if not hasattr(jax.Array, "addressable_shards"):  # pragma: no cover
        pytest.skip("no shard introspection")
    mesh = build_mesh(MeshShape((4, 1, 1, 1)))
    n = 4
    rs = np.random.RandomState(0)
    x = rs.randn(n * n * 2, 6).astype(np.float32)

    out = np.asarray(jax.device_get(
        ring_reduce_scatter(
            jax.device_put(x), mesh=mesh, axis_name="data",
            overlap=overlap)))

    # shard i's local block, split into n chunks; output chunk c = Σ_i block_i[c]
    locals_ = x.reshape(n, x.shape[0] // n, 6)
    chunk = x.shape[0] // n // n
    expect = np.zeros((n * chunk, 6), np.float32)
    for c in range(n):
        expect[c * chunk:(c + 1) * chunk] = sum(
            locals_[i][c * chunk:(c + 1) * chunk] for i in range(n))
    np.testing.assert_allclose(out, expect, rtol=1e-6, atol=1e-6)


def test_sharded_update_pipelined_bit_identical():
    """The sharded update composes with the fused-chunk engine: pinning
    lives in _train_step_body, which IS the chunked scan body, so
    --weight-update-sharding --pipeline-steps 4 equals the eager
    replicated baseline bit-for-bit."""
    x, y = _data(64)

    rep = _mlp(argv=["--no-weight-update-sharding"])
    rep.fit(x, y, epochs=2, batch_size=8, shuffle=True)

    sh = _mlp(argv=["--weight-update-sharding", "--pipeline-steps", "4"])
    sh.fit(x, y, epochs=2, batch_size=8, shuffle=True)
    assert sh._update_sharding["enabled"] and sh.executor.update_specs
    _assert_bit_equal(_full_state(rep), _full_state(sh))


def test_inference_and_dp1_stay_replicated():
    """No grad sync → no update sharding: a dp=1 (single-chip) compile
    auto-decides replicated with reason no_grad_sync even when forced
    would be legal — and builds no stage-3 gather machinery."""
    ff = _mlp(mesh=(1, 1, 1, 1), argv=[])
    dec = ff._update_sharding
    assert not dec["enabled"] and dec["reason"] == "no_grad_sync"
    assert dec["stage"] == 0
    assert not ff.executor.update_specs
    assert not ff.executor.gather_specs
    assert not ff.executor.gather_schedule

    # inference compile on a dp mesh: no grads, no optimizer state — no
    # update sharding and no stage-3 gathers either
    sys.argv = ["test"]
    from flexflow_tpu import (
        ActiMode, FFConfig, FFModel, LossType, SGDOptimizer,
    )
    from flexflow_tpu.fftype import CompMode

    config = FFConfig()
    config.mesh_axis_sizes = DP4
    config.batch_size = 8
    inf = FFModel(config)
    x = inf.create_tensor((8, 16), name="x")
    t = inf.dense(x, 32, ActiMode.AC_MODE_RELU, name="fc1")
    inf.dense(t, 4, name="fc2")
    inf.compile(optimizer=SGDOptimizer(lr=0.0),
                loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                comp_mode=CompMode.COMP_MODE_INFERENCE)
    dec = inf._update_sharding
    assert not dec["enabled"] and dec["reason"] == "inference"
    assert dec["stage"] == 0
    assert not inf.executor.update_specs
    assert not inf.executor.gather_specs


# ===================================================================
# ZeRO-3 / FSDP stage 3: params sharded at rest + just-in-time gathers
# ===================================================================

@pytest.mark.parametrize("opt", ["adam", "sgd_momentum"])
def test_stage3_bit_identical_trajectory(opt):
    """2 shuffled epochs under forced stage 3 — params sharded at rest,
    each layer's weights all-gathered where it uses them, once a step —
    equal the replicated baseline
    bit-for-bit: params, optimizer slots, counters, step, RNG."""
    x, y = _data(64)

    rep = _mlp(argv=["--weight-update-sharding=off"], opt=opt)
    rep.fit(x, y, epochs=2, batch_size=8, shuffle=True)
    assert not rep._update_sharding["enabled"]

    s3 = _mlp(argv=["--weight-update-sharding=stage3"], opt=opt)
    dec = s3._update_sharding
    assert dec["enabled"] and dec["stage"] == 3 and dec["shards"] == 4
    assert s3.executor.gather_specs, "no weight got a stage-3 gather"
    assert s3.executor.gather_schedule, "no prefetch schedule built"
    # the schedule is one-layer-ahead over the PCG topo order: the first
    # gather hides behind nothing, every later one behind its predecessor
    names = [n for n, _ in s3.executor.gather_schedule]
    behinds = [b for _, b in s3.executor.gather_schedule]
    assert behinds == [None] + names[:-1]
    s3.fit(x, y, epochs=2, batch_size=8, shuffle=True)

    _assert_bit_equal(_full_state(rep), _full_state(s3))


def test_stage3_serial_schedule_bit_identical():
    """--no-overlap-collectives composes with stage 3 (the gather is one
    all-gather a weight either way; the flag keeps its meaning for the
    ring reduce-scatter and ring attention): the values are identical."""
    x, y = _data(64)
    rep = _mlp(argv=["--weight-update-sharding=off"])
    rep.fit(x, y, epochs=1, batch_size=8, shuffle=False)
    s3 = _mlp(argv=["--weight-update-sharding=stage3",
                    "--no-overlap-collectives"])
    assert s3._update_sharding["stage"] == 3
    s3.fit(x, y, epochs=1, batch_size=8, shuffle=False)
    _assert_bit_equal(_full_state(rep), _full_state(s3))


def test_stage3_pipelined_bit_identical():
    """Stage 3 composes with the fused-chunk engine: the gathers live in
    _train_step_body's _apply, which IS the chunked scan body, so
    --weight-update-sharding=stage3 --pipeline-steps 4 equals the eager
    replicated baseline bit-for-bit."""
    x, y = _data(64)

    rep = _mlp(argv=["--weight-update-sharding=off"])
    rep.fit(x, y, epochs=2, batch_size=8, shuffle=True)

    s3 = _mlp(argv=["--weight-update-sharding=stage3",
                    "--pipeline-steps", "4"])
    s3.fit(x, y, epochs=2, batch_size=8, shuffle=True)
    assert s3._update_sharding["stage"] == 3 and s3.executor.gather_specs
    _assert_bit_equal(_full_state(rep), _full_state(s3))


def test_stage3_params_live_1_over_shards_at_rest():
    """The at-rest layout really is ZeRO-3: measured over the process's
    LIVE arrays (jax.live_arrays — actual allocations, not specs), each
    stage-3 param stores every byte exactly once across the mesh's
    devices, where the replicated baseline stores it once PER CHIP; and
    chip 0's addressable share is 1/shards of the logical bytes."""
    import jax

    def param_bytes(ff, key):
        leaf = ff._params[key[0]][key[1]]
        live = [a for a in jax.live_arrays() if a is leaf]
        assert live, f"{key} not among live arrays"
        arr = live[0]
        total = sum(int(s.data.size) * s.data.dtype.itemsize
                    for s in arr.addressable_shards)
        dev0 = jax.devices()[0]
        on0 = sum(int(s.data.size) * s.data.dtype.itemsize
                  for s in arr.addressable_shards if s.device == dev0)
        logical = int(np.prod(arr.shape)) * arr.dtype.itemsize
        return total, on0, logical

    rep = _mlp(argv=["--weight-update-sharding=off"])
    s3 = _mlp(argv=["--weight-update-sharding=stage3"])
    assert s3.executor.update_specs
    for key in s3.executor.update_specs:
        tot_r, on0_r, logical = param_bytes(rep, key)
        tot_s, on0_s, _ = param_bytes(s3, key)
        assert tot_r == 4 * logical and on0_r == logical  # replicated ×4
        assert tot_s == logical, key  # every byte stored once
        assert on0_s * 4 == logical, key  # 1/shards per chip
    # optimizer slots shrank identically
    for slot_tree in s3._opt_slots.values():
        s = slot_tree["fc1"]["kernel"]
        assert s.addressable_shards[0].data.size * 4 == s.size


def test_stage3_kill_resume_across_stage_toggles(tmp_path):
    """Elastic resume across stage2↔stage3↔off toggles on one mesh:
    checkpoints hold full logical arrays, so each restoring compile
    re-places them under ITS OWN stage — the whole chain stays bit-equal
    to an uninterrupted replicated run."""
    import jax

    from flexflow_tpu.resilience import FaultInjector, SimulatedPreemption

    x, y = _data(64)
    root = str(tmp_path / "ck")

    ref = _mlp(argv=["--weight-update-sharding=off"])
    ref.fit(x, y, epochs=3, batch_size=8, shuffle=True)

    # leg 1: stage 3, dies at step 5 (last commit: 4)
    ff1 = _mlp(argv=["--weight-update-sharding=stage3",
                     "--checkpoint-dir", root, "--checkpoint-every", "2"])
    assert ff1._update_sharding["stage"] == 3
    ff1.set_fault_hook(FaultInjector(kill_after_step=5))
    with pytest.raises(SimulatedPreemption):
        ff1.fit(x, y, epochs=3, batch_size=8, shuffle=True)
    del ff1

    # leg 2: stage 2 resume, finishes epoch 2, saves (manifest: stage 2)
    ff2 = _mlp(argv=["--weight-update-sharding=stage2",
                     "--checkpoint-dir", root, "--auto-resume"])
    assert ff2._update_sharding["stage"] == 2
    assert not ff2.executor.gather_specs
    ff2.fit(x, y, epochs=2, batch_size=8, shuffle=True)
    ff2._resilience.save(int(np.asarray(jax.device_get(ff2._step))),
                         cursor={"epoch": 2, "batch": 0}, blocking=True)
    mani = ff2._resilience.peek_latest()[1]
    assert mani["update_sharding"]["stage"] == 2
    del ff2

    # leg 3: replicated resume for epoch 3's first half... then back to
    # stage 3 — exercised as one final leg to keep the test fast
    ff3 = _mlp(argv=["--weight-update-sharding=stage3",
                     "--checkpoint-dir", root, "--auto-resume"])
    assert ff3._update_sharding["stage"] == 3
    ff3.fit(x, y, epochs=3, batch_size=8, shuffle=True)
    _assert_bit_equal(_full_state(ref), _full_state(ff3))


def test_memory_pressure_flips_auto_decision_to_stage3():
    """Auto mode: with the per-chip cap squeezed between stage 3's
    footprint and stage 2's (stage 2 keeps one resident gathered copy
    per weight — model bytes flat in dp), the decision must escalate to
    stage 3 with reason memory_bound; with the cap relaxed above
    stage 2, it must NOT escalate. Uses a 6-hidden-layer MLP: past ~3
    layers the two-gathered-layers-in-flight transient undercuts the
    per-weight resident copies, which is exactly when stage 3 wins."""
    probe = _mlp(argv=[], depth=6)  # price once: find stage boundaries
    pred = probe._update_sharding["predicted"]
    s2, s3 = pred["stage2_mem_bytes"], pred["stage3_mem_bytes"]
    assert s3 < s2
    mid_mib = (s2 + s3) / 2 / 2**20

    ff = _mlp(argv=["-ll:fsize", f"{mid_mib:.6f}"], depth=6)
    dec = ff._update_sharding
    assert dec["forced"] is None
    assert dec["enabled"] and dec["stage"] == 3
    assert dec["reason"] == "memory_bound"
    p = dec["predicted"]
    assert p["stage2_mem_bytes"] > p["hbm_cap_bytes"]
    assert p["stage3_mem_bytes"] <= p["hbm_cap_bytes"]
    assert ff.executor.gather_specs

    above_mib = s2 * 1.5 / 2**20
    ff2 = _mlp(argv=["-ll:fsize", f"{above_mib:.6f}"], depth=6)
    assert ff2._update_sharding["stage"] != 3


def test_programmatic_stage_pin_in_auto_mode():
    """config.weight_update_stage alone (sharding left None) pins the
    stage while enablement stays auto: on a memory-bound cap that
    auto-picks stage 3, stage=2 caps the escalation (still enabled),
    stage=0 forces replicated — the documented 0/2/3 = forced
    contract. The pinned plans may legitimately trip the OOM gate (they
    really don't fit), so the probe compiles with verify off."""
    import sys as _sys

    def build(stage=None, fsize=None):
        _sys.argv = (["test"] + (["-ll:fsize", fsize] if fsize else []))
        from flexflow_tpu import (
            ActiMode, AdamOptimizer, FFConfig, FFModel, LossType,
        )

        config = FFConfig()
        config.mesh_axis_sizes = DP4
        config.batch_size = 8
        config.weight_update_stage = stage
        if stage is not None:
            config.verify_plan = False
        ff = FFModel(config)
        x = ff.create_tensor((8, 16), name="x")
        t = ff.dense(x, 32, ActiMode.AC_MODE_RELU, name="fc1")
        for i in range(6):
            t = ff.dense(t, 32, ActiMode.AC_MODE_RELU, name=f"h{i}")
        ff.dense(t, 4, name="fc2")
        ff.compile(optimizer=AdamOptimizer(alpha=0.01),
                   loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
        return ff._update_sharding

    pred = build()["predicted"]
    mid = (f"{(pred['stage2_mem_bytes'] + pred['stage3_mem_bytes']) / 2 / 2**20:.6f}")
    auto = build(fsize=mid)
    assert auto["forced"] is None and auto["stage"] == 3
    pin2 = build(stage=2, fsize=mid)
    assert pin2["enabled"] and pin2["stage"] == 2
    pin0 = build(stage=0, fsize=mid)
    assert not pin0["enabled"] and pin0["stage"] == 0


def test_cost_model_prices_stage3_state_and_gathers():
    """CostModel.op_cost under param_gather: per-chip memory drops the
    resident gathered copy (1/shards at rest, gather_bytes carries the
    transient), the grad sync is the RS alone, and the gather pair moves
    the deferred AG twice (fwd + bwd re-gather) — so stage-2's RS+AG
    equals stage-3's RS + half the gather pair, byte for byte."""
    from flexflow_tpu.search.cost_model import CostModel
    from flexflow_tpu.search.machine_model import machine_model_for_mesh
    from flexflow_tpu.search.substitution import _logical_assignment

    ff = _mlp(argv=["--weight-update-sharding=off"])
    node = next(n for n in ff.graph.topo_order()
                if n.name == "fc1" and n.weight_specs)
    cm = CostModel(machine_model_for_mesh(ff.mesh), opt_slots=2)

    def price():
        cm._cache.clear()
        return cm.op_cost(
            node, [_logical_assignment(pt) for pt in node.outputs],
            dict(node.weight_axes),
            [tuple(d.size for d in pt.shape.dims if not d.is_replica_dim)
             for pt in node.inputs],
            [_logical_assignment(pt) for pt in node.inputs])

    cm.update_sharding = True
    s2 = price()
    cm.param_gather = True
    s3 = price()
    assert s2.param_gather_time == 0.0 and s2.gather_bytes == 0.0
    assert s3.param_gather_time > 0.0 and s3.param_gather_hop_s > 0.0
    assert s3.gather_bytes > 0.0
    assert s3.memory < s2.memory
    # the memory delta is exactly the resident gathered copies leaving
    full_wb = sum(float(np.prod(ws.shape)) * 4
                  for ws in node.weight_specs if ws.trainable)
    assert s2.memory - s3.memory == pytest.approx(full_wb, rel=1e-6)
    assert s3.gather_bytes == pytest.approx(full_wb, rel=1e-6)
    # ring-bytes identity: RS+AG == RS + (2·AG)/2
    assert s2.update_sync_time == pytest.approx(
        s3.update_sync_time + s3.param_gather_time / 2, rel=1e-9)


def test_stage3_strategy_report_and_makespan_identity(tmp_path):
    """strategy_report.json under stage 3: update_stage/param_gather_s
    surfaced, the gathers priced on the overlappable channel, and
    verify_report_total still reproduces total_predicted_s — the
    makespan identity extended to the param-gather channel."""
    import json
    import os

    from flexflow_tpu.diagnostics.explain import verify_report_total

    tdir = str(tmp_path / "telemetry")
    x, y = _data(32)
    ff = _mlp(argv=["--weight-update-sharding=stage3", "--diagnostics",
                    "--telemetry-dir", tdir])
    ff.fit(x, y, epochs=1, batch_size=8, shuffle=False)
    ff.get_telemetry().close()

    with open(os.path.join(tdir, "strategy_report.json")) as f:
        report = json.load(f)
    assert report["update_sharding"] is True
    assert report["update_stage"] == 3
    assert report["update_shards"] == 4
    assert report["param_gather_s"] > 0.0
    gathered = [o for o in report["ops"] if o["param_gather_s"] > 0.0]
    assert gathered, "no op carries param_gather_s"
    for o in gathered:
        # gather + grad RS both ride the overlappable channel
        assert o["overlap_s"] >= o["param_gather_s"] + o["grad_sync_s"]
        assert o["sync_s"] == 0.0
    total = verify_report_total(report)
    pred = report["total_predicted_s"]
    assert abs(total - pred) <= 1e-9 + 1e-6 * abs(pred)


def test_stage3_in_plan_fingerprint():
    """The chosen stage is part of the warm-start plan fingerprint: two
    configs differing only in weight_update_stage must not share a plan
    address (the second compile of the SAME config is then a 0-eval
    hit, covered by the warm-start suite)."""
    import sys

    from flexflow_tpu.warmstart.fingerprint import (
        _SEARCH_CONFIG_FIELDS, structural_fingerprint,
    )

    assert "weight_update_stage" in _SEARCH_CONFIG_FIELDS

    ff = _mlp(argv=["--weight-update-sharding=stage3"])
    mesh_axes = {k: int(v) for k, v in ff.mesh.shape.items()}
    fp3 = structural_fingerprint(ff.graph, mesh_axes, ff.config)
    ff.config.weight_update_stage = 2
    fp2 = structural_fingerprint(ff.graph, mesh_axes, ff.config)
    assert fp3 != fp2


def test_memory_liveness_verifies_stage3_accounting():
    """The ffcheck memory-liveness pass models stage 3 as 1/shards
    persistent weights + a two-layers-in-flight gather transient: its
    persistent bytes drop vs stage 2 by exactly the resident gathered
    copies, and the recorded gather peak covers at most the two largest
    adjacent layers."""
    from flexflow_tpu.analysis import memory as mem_pass

    s2 = _mlp(argv=["--weight-update-sharding=stage2"])
    s3 = _mlp(argv=["--weight-update-sharding=stage3"])
    opt_slots = s3.optimizer.num_slots

    m2 = mem_pass.analyze(s2.graph, s2.mesh, opt_slots=opt_slots,
                          update_specs=s2.executor.update_specs,
                          update_stage=2)
    m3 = mem_pass.analyze(s3.graph, s3.mesh, opt_slots=opt_slots,
                          update_specs=s3.executor.update_specs,
                          update_stage=3)
    full_wb = sum(float(np.prod(shape)) * 4
                  for _spec, shape in s3.executor.update_specs.values())
    assert m2["persistent_bytes"] - m3["persistent_bytes"] == \
        pytest.approx(full_wb, rel=1e-6)
    assert 0.0 < m3["gather_peak_bytes"] <= full_wb
    assert m2["gather_peak_bytes"] == 0.0


@pytest.mark.parametrize("mesh_shape,shape,dim,in_spec,out_spec", [
    ((4, 1, 1, 1), (16, 6), 0, ("data", None), (None, None)),
    ((4, 1, 1, 1), (6, 16), 1, (None, "data"), (None, None)),
    # a merged update: the dim carries ('model', 'data'), the gather
    # unwinds 'data' (minor) and leaves the weight's own 'model' shards
    ((2, 2, 1, 1), (16, 6), 0, (("model", "data"), None), ("model", None)),
    ((2, 2, 1, 1), (6, 16), 1, (None, ("model", "data")), (None, "model")),
], ids=["dim0", "dim1", "merged_dim0", "merged_dim1"])
def test_all_gather_matches_reference(mesh_shape, shape, dim, in_spec,
                                      out_spec):
    """parallel.ops.all_gather (the collective a stage-3 weight comes to
    its compute placement by) gives numpy's array back, every device's
    piece where `out_spec` says, along either dim and with the weight's
    other mesh axes carried through."""
    import jax

    from flexflow_tpu.machine import MeshShape, build_mesh
    from flexflow_tpu.parallel.ops import all_gather

    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = build_mesh(MeshShape(mesh_shape))
    x = np.random.RandomState(0).randn(*shape).astype(np.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P(*in_spec)))
    piece = shape[dim] // 4
    assert {s.data.shape[dim] for s in xs.addressable_shards} == {piece}

    out = jax.jit(lambda a: all_gather(
        a, mesh=mesh, axis_name="data", dim=dim,
        in_spec=P(*in_spec), out_spec=P(*out_spec)))(xs)
    np.testing.assert_array_equal(np.asarray(jax.device_get(out)), x)
    want = NamedSharding(mesh, P(*out_spec))
    assert out.sharding.is_equivalent_to(want, x.ndim), out.sharding
    for s in out.addressable_shards:
        np.testing.assert_array_equal(np.asarray(s.data), x[s.index])


def test_stage3_donated_gather_executable():
    """build_param_gather: one donated dispatch gathers the whole
    sharded-at-rest tree back to full logical values (callers rebind the
    donated tree — the carry pattern the donation lint enforces)."""
    import jax

    rep = _mlp(argv=["--weight-update-sharding=off"], seed=3)
    s3 = _mlp(argv=["--weight-update-sharding=stage3"], seed=3)
    assert s3.executor.gather_specs
    gather_fn = s3.executor.build_param_gather()
    tree = {k: dict(v) for k, v in s3._params.items()}
    tree = gather_fn(tree)
    for (node, wname) in s3.executor.gather_specs:
        got = np.asarray(jax.device_get(tree[node][wname]))
        want = np.asarray(jax.device_get(rep._params[node][wname]))
        np.testing.assert_array_equal(got, want, err_msg=f"{node}.{wname}")
