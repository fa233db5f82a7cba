"""ZeRO-3 / FSDP smoke: memory-constrained LM on a dp CPU mesh.

The CI gate for stage-3 parameter sharding (docs/performance.md
"Parameter sharding (ZeRO-3/FSDP)"): compiles a small transformer LM on
a pure data-parallel mesh with per-chip HBM capped below what STAGE 2
can fit (stage 2 keeps one resident gathered copy per weight, so its
model bytes are flat in dp), WITHOUT forcing --weight-update-sharding,
runs a short fit, then asserts

  - Unity's update-dimension decision (choose_update_sharding) SELECTED
    stage 3 on its own: auto mode (forced is None), reason memory_bound,
    predicted stage-2 memory over the cap and predicted stage-3 memory
    under it (1/shards-at-rest weights + at most two gathered layers in
    flight is what fits the plan);
  - the params really live 1/shards at rest: the addressable parameter
    bytes on chip 0 are ~1/shards of the logical parameter bytes;
  - the donated param-gather executable round-trips: gathering the
    (donated, rebound) tree reproduces the full logical values;
  - the strategy report prices the per-layer gathers on the overlappable
    channel: update_stage 3, report-level param_gather_s > 0, and every
    op that carries param_gather_s shows overlap_s >= param_gather_s
    with sync_s == 0 (the gather is priced as hidden behind the
    previous layer's compute; only its issue latency is exposed);
  - the makespan identity still reproduces with the gather channel in
    play (run_doctor --check covers the same report in CI);
  - the ffcheck memory-liveness pass verified the 1/shards-at-rest +
    transient-gather accounting without tripping the OOM gate on the
    plan the decision made fit;
  - telemetry carries the param_gather event (layers/bytes/collective) and
    the weight_update event with stage 3 — the compiled executable
    really runs the just-in-time gathers;
  - the fit completed (steps recorded) with stage 3 live.

Usage: python scripts/fsdp_smoke.py --telemetry-dir OUT
       [--mesh 4,1,1,1] [-ll:fsize MiB] [flexflow flags]
Exits nonzero with a diagnostic on any violated assertion.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# virtual 8-device CPU mesh, exactly like tests/conftest.py
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def fail(msg: str):
    print(f"fsdp_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def main():
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models import TransformerLMConfig, build_transformer_lm
    from flexflow_tpu.telemetry import read_jsonl

    # defaults: a dp=4 mesh and a per-chip HBM cap squeezed below what
    # stage 2's resident gathered copies can fit — auto mode must flip
    # to stage 3 (NO --weight-update-sharding here: the point is that
    # Unity selects it)
    argv = sys.argv[1:]
    if any(a.startswith("--weight-update-sharding") for a in argv):
        fail("do not force --weight-update-sharding — the smoke proves "
             "the search selects stage 3")
    if "--mesh" not in argv:
        argv += ["--mesh", "4,1,1,1"]
    if "-ll:fsize" not in argv:
        argv += ["-ll:fsize", "0.9"]
    if "--diagnostics" not in argv:
        argv += ["--diagnostics"]
    sys.argv = [sys.argv[0]] + argv

    config = FFConfig()
    if not config.telemetry_dir:
        fail("pass --telemetry-dir")
    config.batch_size = 4

    ff = FFModel(config)
    cfg = TransformerLMConfig(
        vocab_size=128, hidden_size=64, num_heads=2, num_layers=2,
        sequence_length=32)
    build_transformer_lm(ff, cfg, batch_size=4)
    ff.compile(optimizer=SGDOptimizer(lr=0.01, momentum=0.9),
               loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)

    # 1) the update-dimension search selected stage 3, for the memory
    # reason, in auto mode
    dec = ff._update_sharding or {}
    if dec.get("forced") is not None:
        fail(f"decision was forced ({dec['forced']}) — auto mode required")
    if not dec.get("enabled") or dec.get("stage") != 3:
        fail(f"search did not select stage 3 (stage {dec.get('stage')}, "
             f"reason {dec.get('reason')}): {dec.get('predicted')}")
    if dec.get("reason") != "memory_bound":
        fail(f"expected a memory_bound selection, got {dec.get('reason')}")
    pred = dec.get("predicted") or {}
    cap = pred.get("hbm_cap_bytes", 0.0)
    if not (pred.get("stage2_mem_bytes", 0.0) > cap
            >= pred.get("stage3_mem_bytes", float("inf"))):
        fail(f"memory pricing inconsistent with a stage-3 memory_bound "
             f"pick: stage2 {pred.get('stage2_mem_bytes')} / stage3 "
             f"{pred.get('stage3_mem_bytes')} vs cap {cap}")
    if not ff.executor.gather_specs or not ff.executor.gather_schedule:
        fail("stage 3 selected but the executor built no gather schedule")

    # 2) the params live 1/shards at rest: addressable bytes on chip 0
    # vs the logical parameter bytes of the sharded weights
    shards = dec["shards"]
    dev0 = jax.devices()[0]
    sharded_logical = 0
    sharded_local = 0
    for (node, wname), (_spec, shape) in ff.executor.update_specs.items():
        leaf = ff._params[node][wname]
        sharded_logical += int(np.prod(shape)) * 4
        for sh in leaf.addressable_shards:
            if sh.device == dev0:
                sharded_local += int(sh.data.size) * sh.data.dtype.itemsize
    if not sharded_logical or \
            sharded_local > sharded_logical / shards * 1.01:
        fail(f"at-rest layout is not 1/shards: {sharded_local} bytes on "
             f"chip 0 vs {sharded_logical} logical / {shards} shards")

    # 3) the donated gather executable round-trips (rebind pattern —
    # the tree is donated, so it is reassigned from the call)
    before = {
        # two one-off reference fetches at setup, not a hot loop
        key: np.asarray(jax.device_get(ff._params[key[0]][key[1]]))  # fflint: ok host_sync_in_loop
        for key in list(ff.executor.gather_specs)[:2]}
    gather_fn = ff.executor.build_param_gather()
    tree = {k: dict(v) for k, v in ff._params.items()}
    tree = gather_fn(tree)
    for (node, wname), want in before.items():
        # two one-off verification fetches at setup, not a hot loop
        got = np.asarray(jax.device_get(tree[node][wname]))  # fflint: ok host_sync_in_loop
        if not np.array_equal(got, want):
            fail(f"gathered {node}.{wname} != logical values")
    ff._params = tree  # gathered values == logical values, placement differs
    # same-model round-trip of the values just gathered above — not a
    # plan transition (no second plan exists to verify against)
    ff._params = ff.executor.place_update_sharded(ff._params)  # fflint: ok unverified_transition

    rs = np.random.RandomState(0)
    n = 8
    X = {"tokens": rs.randint(0, cfg.vocab_size,
                              (n, cfg.sequence_length)).astype(np.int32),
         "positions": np.tile(
             np.arange(cfg.sequence_length, dtype=np.int32), (n, 1))}
    Y = rs.randint(0, cfg.vocab_size,
                   (n, cfg.sequence_length, 1)).astype(np.int32)
    ff.fit(X, Y, epochs=1, batch_size=4, shuffle=False, verbose=False)

    tdir = config.telemetry_dir
    report_path = os.path.join(tdir, "strategy_report.json")
    if not os.path.exists(report_path):
        fail(f"missing strategy report {report_path}")
    with open(report_path) as f:
        report = json.load(f)

    # 4) the report prices the per-layer gathers on the overlappable
    # channel
    if report.get("update_stage") != 3:
        fail(f"strategy report update_stage {report.get('update_stage')} "
             f"!= 3")
    if report.get("update_shards") != dec["shards"]:
        fail(f"report update_shards {report.get('update_shards')} != "
             f"decision shards {dec['shards']}")
    if not report.get("param_gather_s", 0.0) > 0.0:
        fail("report param_gather_s is zero — the gathers were not "
             "priced on the stage-3 channel")
    gathered_ops = [o for o in report["ops"]
                    if o.get("param_gather_s", 0.0) > 0.0]
    if not gathered_ops:
        fail("no op carries param_gather_s")
    for o in gathered_ops:
        if (o.get("overlap_s", 0.0)
                < o["param_gather_s"] + o.get("grad_sync_s", 0.0)
                or o.get("sync_s")):
            fail(f"op {o['name']} gather not on the overlappable "
                 f"channel: overlap_s {o.get('overlap_s')} / "
                 f"param_gather_s {o['param_gather_s']} / sync_s "
                 f"{o.get('sync_s')}")

    # 5) the report's makespan identity holds with the gather channel
    from flexflow_tpu.diagnostics.explain import verify_report_total

    total = verify_report_total(report)
    pred_s = report["total_predicted_s"]
    if not (abs(total - pred_s) <= 1e-9 + 1e-6 * abs(pred_s)):
        fail(f"makespan identity broken with the param-gather channel: "
             f"verify={total} vs report={pred_s}")

    # 6) ffcheck's memory-liveness pass verified the stage-3 accounting
    # and did not trip the OOM gate on the plan the decision made fit
    analysis = report.get("analysis") or {}
    if analysis.get("errors", 1) != 0:
        fail(f"ffcheck reported errors on the stage-3 plan: {analysis}")
    mem_findings = [f for f in analysis.get("findings", [])
                    if f.get("code") == "memory_timeline"]
    if not mem_findings:
        fail("no memory_timeline finding — the liveness pass did not run")
    details = mem_findings[0].get("details") or {}
    if details.get("update_stage") != 3:
        fail(f"liveness pass did not see stage 3: {details}")
    if not details.get("gather_peak_bytes", 0.0) > 0.0:
        fail("liveness pass recorded no transient gather bytes")
    if [f for f in analysis.get("findings", [])
            if f.get("code") == "oom_predicted"
            and f.get("severity") == "error"]:
        fail("OOM gate fired on the plan the stage-3 decision made fit")

    # 7) the compiled executable really runs the gathers
    recs = list(read_jsonl(os.path.join(tdir, "metrics.jsonl")))
    pg = [r for r in recs if r.get("kind") == "param_gather"]
    if not pg:
        fail("no param_gather event in telemetry")
    if not (pg[0].get("layers") and pg[0].get("bytes")
            and pg[0].get("gathers_per_step")):
        fail(f"param_gather event inconsistent: {pg[0]}")
    wu = [r for r in recs if r.get("kind") == "weight_update"]
    if not wu or wu[0].get("stage") != 3:
        fail(f"weight_update event missing stage 3: {wu[:1]}")

    # 8) the fit actually stepped under stage 3
    steps = [r for r in recs if r.get("kind") == "step"]
    if not steps:
        fail("no step records — fit did not run")

    print(f"fsdp_smoke: OK — stage 3 selected "
          f"({dec['shards']} shards, reason {dec['reason']}; "
          f"mem stage2 {pred['stage2_mem_bytes'] / 2**20:.2f} -> stage3 "
          f"{pred['stage3_mem_bytes'] / 2**20:.2f} MiB/chip vs cap "
          f"{cap / 2**20:.2f}), params {sharded_local} B/chip at rest "
          f"(~1/{dec['shards']} of {sharded_logical} B), param_gather_s "
          f"{report['param_gather_s'] * 1e6:.1f} us overlapped, "
          f"{len(steps)} steps, makespan identity holds")


if __name__ == "__main__":
    main()
