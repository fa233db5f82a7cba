"""Benchmark: flagship Transformer LM training throughput on one chip.

Mirrors the reference's benchmark harness (examples/cpp/Transformer/
transformer.cc:183-211: timed training loop printing ELAPSED TIME /
THROUGHPUT) with the reference model scale (hidden 1024, 16 heads, 12
layers, seq 512 — TransformerConfig, transformer.cc:79-85) recast as the
decoder-only LM, and adds the MFU accounting BASELINE.md targets.

Prints the primary JSON line
  {"metric": "transformer_lm_tokens_per_sec_per_chip", "value": N,
   "unit": "tokens/s", "vs_baseline": MFU / 0.35}
**LAST** — the driver parses the LAST line as the number of record, so any
secondary legs (the TPU seq-4096 long-context leg) print before it.
(vs_baseline = fraction of the 35%-MFU north-star target, BASELINE.json.)
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def _peak_flops(device) -> float:
    """bf16 peak of `device` from the machine model's one table; a device
    that is not in it raises."""
    from flexflow_tpu.search.machine_model import chip_for

    return chip_for(device).peak_flops


def _hbm_stats(device) -> dict:
    """{peak_hbm_bytes, hbm_bytes_in_use} from the backend allocator, or
    {} when the platform has no memory_stats (XLA:CPU)."""
    try:
        stats = device.memory_stats()
    except Exception:  # pragma: no cover - platform-dependent
        stats = None
    if not stats:
        return {}
    out = {}
    if stats.get("peak_bytes_in_use") is not None:
        out["peak_hbm_bytes"] = int(stats["peak_bytes_in_use"])
    if stats.get("bytes_in_use") is not None:
        out["hbm_bytes_in_use"] = int(stats["bytes_in_use"])
    return out


def _addressable_bytes_per_chip(tree) -> int:
    """Bytes of `tree`'s leaves resident on device 0 — the per-chip
    at-rest footprint a sharded layout actually achieves (replicated
    leaves count in full; 1/shards leaves count their one shard)."""
    import jax

    dev0 = jax.devices()[0]
    total = 0
    for leaf in jax.tree.leaves(tree):
        for sh in getattr(leaf, "addressable_shards", ()):
            if sh.device == dev0:
                total += int(sh.data.size) * sh.data.dtype.itemsize
    return total


def _measure_lm(cfg, batch: int, steps: int, warmup: int, on_tpu: bool,
                tune=None, out: dict = None):
    """(tokens/s, MFU) of one LM training config, or (None, None) when
    every retry reads as a backend fluke (>100% MFU). `tune(config)`, when
    given, mutates the FFConfig before the model is built — the ablation
    legs use it to flip kernel layout / collective-overlap / mesh knobs
    against an otherwise identical measurement. `out`, when a dict, is
    filled with the leg's memory forensics: allocator stats after warmup
    (resident state incl. masters + optimizer slots — the reading the
    weight-update-sharding ablation compares) and the compile's
    update-sharding decision."""
    import jax

    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models import build_transformer_lm
    from flexflow_tpu.models.transformer import transformer_lm_flops_per_token

    from flexflow_tpu import telemetry

    config = FFConfig()
    config.batch_size = batch
    if on_tpu:
        # full mixed-precision policy: bf16 activations, fp32 master weights
        from flexflow_tpu.fftype import DataType

        config.computation_dtype = DataType.DT_BFLOAT16
    if tune is not None:
        tune(config)
    ff = FFModel(config)
    build_transformer_lm(ff, cfg, batch_size=batch)
    with telemetry.span("bench.compile", seq=cfg.sequence_length):
        ff.compile(optimizer=SGDOptimizer(lr=0.01),
                   loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
        step_fn = ff.executor.build_train_step()

    rs = np.random.RandomState(0)
    toks = rs.randint(0, cfg.vocab_size,
                      (batch, cfg.sequence_length)).astype(np.int32)
    pos = np.tile(np.arange(cfg.sequence_length, dtype=np.int32), (batch, 1))
    labels = rs.randint(0, cfg.vocab_size,
                        (batch, cfg.sequence_length, 1)).astype(np.int32)
    batch_data = ff._make_batch({"tokens": toks, "positions": pos}, labels)

    import statistics

    import jax.numpy as jnp

    state = (ff._params, ff._state, ff._opt_slots, ff._step, ff._counters)
    rng = jax.random.key(0)

    # Two-point slope measurement: the whole measured run is ONE jitted
    # fori_loop of train steps (the Legion begin_trace/end_trace replay
    # loop, transformer.cc:183-197, collapsed into a single executable —
    # per-step host dispatch cannot pollute the reading) with a DYNAMIC
    # trip count, synchronized by fetching the step counter, timed at n
    # and 3n steps — the slope is the per-step device time with every
    # per-call constant (dispatch, the fetch) cancelled. This is the
    # device-side ceiling; what a training job sees is the fit-loop leg.
    def loop_fn():
        @jax.jit
        def loop(st, r, batch, n):
            def body(_, carry):
                st, r = carry
                r, sub = jax.random.split(r)
                out = step_fn(*st, sub, batch)
                return (out[:5], r)

            return jax.lax.fori_loop(0, n, body, (st, r))

        return loop

    loop = loop_fn()

    def sync(st):
        return int(jax.device_get(st[3]))  # step counter: forces completion

    with telemetry.span("bench.warmup", steps=warmup):
        st, rng = loop(state, rng, batch_data, jnp.int32(warmup))
        sync(st)  # compile + warm

    if out is not None:
        out.update(_hbm_stats(jax.devices()[0]))
        upd = getattr(ff, "_update_sharding", None) or {}
        out["update_sharding"] = bool(upd.get("enabled"))
        out["update_stage"] = int(upd.get("stage", 0))
        out["update_shards"] = int(upd.get("shards", 1))
        # addressable parameter bytes on chip 0 AT REST — the reading
        # the stage-3 1/shards layout shrinks (stage ≤ 2 keeps it flat)
        out["addressable_param_bytes_per_chip"] = (
            _addressable_bytes_per_chip(ff._params))
        pred = upd.get("predicted") or {}
        if pred:
            out["predicted_mem_bytes_per_chip"] = (
                pred["sharded_mem_bytes"] if upd.get("enabled")
                else pred["replicated_mem_bytes"])

    def t_of(n, st, rng):
        ts = []
        with telemetry.span("bench.measure", steps=n):
            for _ in range(3):
                t0 = time.perf_counter()
                st, rng = loop(st, rng, batch_data, jnp.int32(n))
                sync(st)
                ts.append(time.perf_counter() - t0)
        return statistics.median(ts), st, rng

    flops_per_token = transformer_lm_flops_per_token(cfg)
    peak = _peak_flops(jax.devices()[0])
    # guard against measurement flukes (a negative or implausible slope
    # from host jitter between the two timings): retry until plausible
    for _ in range(3):
        t1, st, rng = t_of(steps, st, rng)
        t2, st, rng = t_of(3 * steps, st, rng)
        per_step = (t2 - t1) / (2 * steps)
        if per_step <= 0:
            continue
        tokens_per_sec = batch * cfg.sequence_length / per_step
        mfu = tokens_per_sec * flops_per_token / peak
        if not on_tpu or mfu <= 1.0:
            return tokens_per_sec, mfu
    return None, None


def _measure_fit_loop(cfg, batch: int, batches_per_epoch: int,
                      epochs_timed: int, pipeline_steps: int, on_tpu: bool):
    """tokens/s of the REAL `fit` loop — the throughput training jobs
    actually see, unlike the scan-slope leg's device-time ceiling.
    pipeline_steps=1 is the eager per-step loop; >1 routes through the
    pipelined engine (fused chunk dispatch + async prefetch, engine/).
    The gap between this leg and the slope metric is the dispatch +
    input-pipeline overhead the engine exists to remove."""
    import time as _time

    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu import telemetry
    from flexflow_tpu.models import build_transformer_lm

    config = FFConfig()
    config.batch_size = batch
    if on_tpu:
        from flexflow_tpu.fftype import DataType

        config.computation_dtype = DataType.DT_BFLOAT16
    ff = FFModel(config)
    build_transformer_lm(ff, cfg, batch_size=batch)
    with telemetry.span("bench.fit.compile", pipeline_steps=pipeline_steps):
        ff.compile(optimizer=SGDOptimizer(lr=0.01),
                   loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)

    n = batches_per_epoch * batch
    rs = np.random.RandomState(0)
    x = {
        "tokens": rs.randint(0, cfg.vocab_size,
                             (n, cfg.sequence_length)).astype(np.int32),
        "positions": np.tile(
            np.arange(cfg.sequence_length, dtype=np.int32), (n, 1)),
    }
    labels = rs.randint(0, cfg.vocab_size,
                        (n, cfg.sequence_length, 1)).astype(np.int32)

    fit_kw = dict(batch_size=batch, shuffle=False, verbose=False,
                  pipeline_steps=pipeline_steps)
    with telemetry.span("bench.fit.warmup", pipeline_steps=pipeline_steps):
        ff.fit(x, labels, epochs=1, **fit_kw)  # compile + warm
    with telemetry.span("bench.fit.measure", pipeline_steps=pipeline_steps):
        t0 = _time.perf_counter()
        ff.fit(x, labels, epochs=epochs_timed, **fit_kw)
        dt = _time.perf_counter() - t0
    tokens = epochs_timed * batches_per_epoch * batch * cfg.sequence_length
    return tokens / dt


def _fit_loop_legs(cfg, batch: int, on_tpu: bool,
                   pipeline_steps: int = 4) -> dict:
    """Eager + pipelined fit-loop legs; archived in the BENCH json (the
    payload's fit_loop field) so the bench-vs-fit gap stays tracked. On
    TPU the flagship model runs as-is (per-step host dispatch and input
    staging are the overhead under test); the CPU smoke swaps in a
    dispatch-bound config — local-CPU dispatch is ~50 µs, so against the
    smoke model's ~40 ms steps the loop overhead the engine removes
    would be invisible noise."""
    from flexflow_tpu.models import TransformerLMConfig

    if on_tpu:
        batches_per_epoch, epochs_timed = 16, 2
    else:
        cfg = TransformerLMConfig(
            vocab_size=256, hidden_size=64, num_heads=2, num_layers=1,
            sequence_length=64, attention_impl="xla")
        batch, batches_per_epoch, epochs_timed = 4, 32, 2
    eager = _measure_fit_loop(cfg, batch, batches_per_epoch, epochs_timed,
                              1, on_tpu)
    piped = _measure_fit_loop(cfg, batch, batches_per_epoch, epochs_timed,
                              pipeline_steps, on_tpu)
    return {
        "eager_tokens_per_sec": round(eager, 2),
        "pipelined_tokens_per_sec": round(piped, 2),
        "pipeline_steps": pipeline_steps,
        "speedup": round(piped / eager, 4) if eager > 0 else None,
    }


def _attention_ablation_legs(lcfg, batch: int, steps: int, warmup: int,
                             on_tpu: bool) -> dict:
    """seq-4096 attention-ablation legs (docs/performance.md
    "Long-context path"): ring_overlap vs ring_serial, the
    sequence-parallel ring path with the double-buffered
    hop-before-compute ppermute pipeline vs the serial compute-then-hop
    ablation (--no-overlap-collectives), seq axis sharded over every
    local device. Skipped (null) on one chip — there is no ring to
    overlap. Both legs reuse the slope methodology of `_measure_lm`."""
    import dataclasses

    import jax

    legs = {}
    n = jax.local_device_count()
    if n > 1:
        rcfg = dataclasses.replace(lcfg, attention_impl="ring")

        def ring_tune(overlap):
            def tune(c):
                c.mesh_axis_sizes = (1, 1, 1, n)  # data,model,pipe,seq
                c.enable_sample_parallel = True
                c.search_budget = 4
                c.overlap_collectives = overlap

            return tune

        for name, overlap in (("ring_overlap", True),
                              ("ring_serial", False)):
            tps_r, _ = _measure_lm(rcfg, batch, steps, warmup, on_tpu,
                                   tune=ring_tune(overlap))
            legs[f"{name}_tokens_per_sec"] = (
                None if tps_r is None else round(tps_r, 2))
        ro = legs.get("ring_overlap_tokens_per_sec")
        rs = legs.get("ring_serial_tokens_per_sec")
        if ro and rs:
            legs["overlap_vs_serial"] = round(ro / rs, 4)
        legs["ring_seq_shards"] = n
    else:
        legs["ring_overlap_tokens_per_sec"] = None
        legs["ring_serial_tokens_per_sec"] = None
    return legs


def _grad_sync_legs(cfg, batch: int, steps: int, warmup: int,
                    on_tpu: bool) -> dict:
    """Weight-update-sharding ablation (round 8, docs/performance.md
    "Weight-update sharding"): the same LM on a pure-dp mesh over all
    local devices, measured three ways —

    - replicated: the baseline serial gradient allreduce + every replica
      redundantly holding fp32 masters + optimizer slots and running the
      full update (--no-weight-update-sharding);
    - sharded_overlap: ZeRO-style 1/dp update with the grad reduce-scatter
      free to overlap backward compute and the updated-param all-gather
      deferred into each consumer's first use (--weight-update-sharding);
    - sharded_serial: same 1/dp state, overlap pricing/schedule off
      (--no-overlap-collectives) — isolates the overlap contribution from
      the memory win.

    Each leg records the allocator's resident bytes after warmup (masters
    + slots live there — the 1/dp saving shows up directly) next to its
    tokens/s. Also includes a ring_reduce_scatter microbench: the
    free-scheduled ppermute pipeline vs the barrier-forced serial
    hop-then-add ablation on a gradient-sized buffer — the schedule the
    sharded grad sync lowers to, measured in isolation."""
    import jax

    n = min(jax.local_device_count(), batch)
    legs = {"update_shards": n}
    if n <= 1:
        legs["skipped"] = "single device — no grad sync to shard"
        return legs

    def dp_tune(wus, overlap=True):
        def tune(c):
            c.mesh_axis_sizes = (n, 1, 1, 1)
            c.weight_update_sharding = wus
            c.overlap_collectives = overlap

        return tune

    for name, wus, overlap in (("replicated", False, True),
                               ("sharded_overlap", True, True),
                               ("sharded_serial", True, False)):
        mem: dict = {}
        tps, _ = _measure_lm(cfg, batch, steps, warmup, on_tpu,
                             tune=dp_tune(wus, overlap), out=mem)
        legs[f"{name}_tokens_per_sec"] = (
            None if tps is None else round(tps, 2))
        if "hbm_bytes_in_use" in mem:
            legs[f"{name}_hbm_bytes_in_use"] = mem["hbm_bytes_in_use"]
        if "predicted_mem_bytes_per_chip" in mem:
            legs[f"{name}_predicted_mem_bytes_per_chip"] = round(
                mem["predicted_mem_bytes_per_chip"])
    so, rep = (legs.get("sharded_overlap_tokens_per_sec"),
               legs.get("replicated_tokens_per_sec"))
    ss = legs.get("sharded_serial_tokens_per_sec")
    if so and rep:
        legs["sharded_overlap_vs_replicated"] = round(so / rep, 4)
    if so and ss:
        legs["overlap_vs_serial"] = round(so / ss, 4)

    try:
        legs["rs_microbench"] = _ring_rs_microbench(n)
    except Exception as e:  # pragma: no cover - defensive
        print(f"bench: ring-RS microbench failed: {e}", file=sys.stderr)
    return legs


def _ring_rs_microbench(n: int, rows: int = 4096, cols: int = 512,
                        iters: int = 8) -> dict:
    """Seconds per reduce-scatter of a (rows, cols) fp32 buffer over a
    dp=n mesh: the free-scheduled ppermute pipeline
    (parallel.ops.ring_reduce_scatter — each hop independent of the
    local chunk add beside it) vs the serial ablation whose
    optimization barrier forces every add to wait for its hop. Two-point
    slope over a jitted fori_loop, like every other bench leg."""
    import functools

    import jax
    import jax.numpy as jnp

    from flexflow_tpu.machine import MeshShape, build_mesh
    from flexflow_tpu.parallel.ops import ring_reduce_scatter

    rows -= rows % (n * n)
    mesh = build_mesh(MeshShape((n, 1, 1, 1)))
    x = jnp.arange(rows * cols, dtype=jnp.float32).reshape(rows, cols)
    out = {}
    for name, overlap in (("overlap", True), ("serial", False)):
        rs = functools.partial(ring_reduce_scatter, mesh=mesh,
                               axis_name="data", overlap=overlap)

        @jax.jit
        def loop(x0, m):
            def body(_, acc):
                # rescale so the collective (not the arithmetic) dominates
                # and the loop-carried value stays finite
                return jnp.tile(rs(acc) * 1e-3, (n, 1))

            return jax.lax.fori_loop(0, m, body, x0)

        jax.block_until_ready(loop(x, jnp.int32(iters)))  # compile + warm
        t1 = time.perf_counter()
        jax.block_until_ready(loop(x, jnp.int32(iters)))
        t1 = time.perf_counter() - t1
        t2 = time.perf_counter()
        jax.block_until_ready(loop(x, jnp.int32(3 * iters)))
        t2 = time.perf_counter() - t2
        out[f"{name}_s"] = max((t2 - t1) / (2 * iters), 0.0)
    if out.get("serial_s"):
        out["overlap_vs_serial"] = round(
            out["serial_s"] / out["overlap_s"], 4) if out["overlap_s"] else None
    out["bytes"] = rows * cols * 4
    return out


def _param_sharding_legs(cfg, batch: int, steps: int, warmup: int,
                         on_tpu: bool) -> dict:
    """ZeRO-3 / FSDP ablation (docs/performance.md "Parameter sharding"):
    the same LM on a pure-dp mesh over all local devices, measured three
    ways —

    - replicated: every chip holds the full model + full optimizer state
      (--weight-update-sharding=off);
    - stage2: masters/grads/slots 1/dp, params gathered-and-resident
      (=stage2);
    - stage3: params sharded at rest, each layer's weights all-gathered
      where the layer uses them, once a step (=stage3).

    Each leg reports tokens/s, per-step seconds, ADDRESSABLE param bytes
    on chip 0 at rest (the 1/shards reading), allocator peak HBM (null
    on XLA:CPU), and the realized update stage."""
    import jax

    n = min(jax.local_device_count(), batch)
    legs = {"shards": n}
    if n <= 1:
        legs["skipped"] = "single device — nothing to shard"
        return legs

    def tune_of(stage):
        def tune(c):
            c.mesh_axis_sizes = (n, 1, 1, 1)
            c.weight_update_sharding = stage >= 2
            c.weight_update_stage = stage

        return tune

    for name, stage in (("replicated", 0), ("stage2", 2), ("stage3", 3)):
        mem: dict = {}
        tps, _ = _measure_lm(cfg, batch, steps, warmup, on_tpu,
                             tune=tune_of(stage), out=mem)
        legs[name] = {
            "tokens_per_sec": None if tps is None else round(tps, 2),
            "step_time_s": (None if not tps else
                            round(batch * cfg.sequence_length / tps, 6)),
            "addressable_param_bytes_per_chip":
                mem.get("addressable_param_bytes_per_chip"),
            "peak_hbm_bytes": mem.get("peak_hbm_bytes"),
            "update_stage": mem.get("update_stage"),
        }
    rep = legs["replicated"]
    s3 = legs["stage3"]
    if rep.get("addressable_param_bytes_per_chip") and \
            s3.get("addressable_param_bytes_per_chip"):
        legs["param_bytes_ratio"] = round(
            rep["addressable_param_bytes_per_chip"]
            / s3["addressable_param_bytes_per_chip"], 4)
    if rep.get("tokens_per_sec") and s3.get("tokens_per_sec"):
        legs["stage3_vs_replicated"] = round(
            s3["tokens_per_sec"] / rep["tokens_per_sec"], 4)
    return legs


def _rules_leg() -> dict:
    """Rule-registry pin (ffrules, analysis/rules.py): the content
    fingerprint of the STATIC generated rule set (no bench leg builds a
    graph exhibiting the data-driven families, so the static registry is
    exactly what every leg's search rewrote with), plus the wall time of
    the full five-pass verification sweep. Raises if the registry fails
    verification — the caller records the failure as a payload-level
    marker so a capture searched under unsound rules is never mistaken
    for a clean one."""
    from flexflow_tpu import FFConfig
    from flexflow_tpu.analysis import rules as ffrules

    sys.argv = [sys.argv[0]]
    cfg = FFConfig()
    mesh_sizes = {"data": 2, "model": 4, "dcn": 1, "seq": 1}
    cfg.mesh_axis_sizes = tuple(mesh_sizes.values())
    t0 = time.perf_counter()
    res = ffrules.verify_registry(mesh_sizes, cfg)
    wall = time.perf_counter() - t0
    errs = res.errors()
    if errs:
        raise RuntimeError(
            f"rule registry failed verification: "
            f"{[str(f) for f in errs[:3]]}")
    clean = res.by_code("rules_clean")[0]
    return {
        "fingerprint": clean.details["fingerprint"],
        "rules": clean.details["rules"],
        "scope": "static_registry",
        "verify_wall_s": round(wall, 3),
    }


def _warmstart_legs() -> dict:
    """Cold-vs-warm time-to-first-step against one fresh --warmstart-dir
    (compile start → first optimizer step done — the restart latency the
    warm-start subsystem exists to collapse, docs/performance.md "Warm
    start & compile caching"). Archived in the BENCH payload so the
    warm/cold ratio is tracked per round.

    Both legs run in this process, so jax's in-memory compilation
    memoization (keyed by HLO hash) is cleared between them — the warm
    leg must be served by the ON-DISK layers (persistent XLA executable
    cache + plan cache + calibration DB), exactly what a restarted
    process would hit. Multi-chip fleets also exercise the plan cache
    (search + calibration on the cold leg, fingerprint hit on the warm);
    a single-device fleet has no search, so there the legs measure the
    executable-cache layer alone. The warm-start dir is a fixed place
    under the compile cache, emptied before the cold leg. The compile
    cache itself is placed from outside (main), so where it outlives
    the run the cold leg is cold for the plan and calibration layers
    only."""
    import os
    import shutil
    import time as _time

    import jax

    from flexflow_tpu import (
        ActiMode, FFConfig, FFModel, LossType, SGDOptimizer,
    )

    wdir = os.path.join(os.environ["JAX_COMPILATION_CACHE_DIR"],
                        "bench_warmstart")
    shutil.rmtree(wdir, ignore_errors=True)
    multi = jax.device_count() > 1
    batch = 16

    def leg(tag: str) -> float:
        from flexflow_tpu import telemetry

        jax.clear_caches()
        config = FFConfig()
        config.batch_size = batch
        config.warmstart_dir = wdir
        if multi:
            config.search_budget = 4
            config.enable_parameter_parallel = True
            config.search_calibrate = 1
        ff = FFModel(config)
        # explicit names: default layer names embed a process-global guid
        # counter, and the two legs' fingerprints must match
        x = ff.create_tensor((batch, 256), name="ws_x")
        t = x
        for i in range(6):
            t = ff.dense(t, 256, ActiMode.AC_MODE_RELU, name=f"ws_fc{i}")
        ff.dense(t, 32, name="ws_head")
        rs = np.random.RandomState(0)
        X = rs.randn(batch, 256).astype(np.float32)
        Y = rs.randint(0, 32, (batch, 1)).astype(np.int32)
        with telemetry.span("bench.warmstart", leg=tag):
            t0 = _time.perf_counter()
            ff.compile(
                optimizer=SGDOptimizer(lr=0.01),
                loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
            # one optimizer step: first-step latency includes the train
            # step's jit compile + first batch staging
            ff.fit(X, Y, epochs=1, batch_size=batch, shuffle=False,
                   verbose=False)
            dt = _time.perf_counter() - t0
        return dt

    cold = leg("cold")
    warm = leg("warm")
    return {
        "cold_time_to_first_step_s": round(cold, 4),
        "warm_time_to_first_step_s": round(warm, 4),
        "speedup": round(cold / warm, 4) if warm > 0 else None,
    }


def _migration_legs(cfg, on_tpu: bool) -> dict:
    """fftrans migration leg: measured in-process migration seconds vs
    the TransitionPlan's predicted cost (docs/analysis.md "Transition
    verification") — a dp stage-3 trained model migrated live to a
    replicated hybrid mesh, no checkpoint-restart round trip. The
    measured/predicted fidelity ratio is the datapoint the future
    re-planner's pay-off rule needs: a re-shard pays for itself only
    when the predicted migration seconds (this leg calibrates the
    prediction) undercut the drift it removes."""
    import jax

    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models import build_transformer_lm
    from flexflow_tpu.resilience import migrate_state

    n_dev = jax.device_count()
    if n_dev < 4:
        return {"skipped": f"{n_dev} device(s) — no cross-mesh migration"}

    def build(mesh, stage3):
        # argv is restored below: a leg failure must not leak the
        # stage-3 flag into the later warm-start legs' FFConfig parse
        sys.argv = [sys.argv[0]] + (
            ["--weight-update-sharding=stage3"] if stage3 else [])
        config = FFConfig()
        config.mesh_axis_sizes = mesh
        config.batch_size = 4
        ff = FFModel(config)
        build_transformer_lm(ff, cfg, batch_size=4)
        ff.compile(optimizer=SGDOptimizer(lr=0.01, momentum=0.9),
                   loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
        return ff

    saved_argv = list(sys.argv)
    try:
        old = build((4, 1, 1, 1), stage3=True)
        rs = np.random.RandomState(0)
        X = {"tokens": rs.randint(
                0, cfg.vocab_size,
                (4, cfg.sequence_length)).astype(np.int32),
             "positions": np.tile(
                 np.arange(cfg.sequence_length, dtype=np.int32), (4, 1))}
        Y = rs.randint(0, cfg.vocab_size,
                       (4, cfg.sequence_length, 1)).astype(np.int32)
        old.fit(X, Y, epochs=1, batch_size=4, shuffle=False,
                verbose=False)
        new = build((2, 2, 1, 1), stage3=False)
        section = migrate_state(old, new)
    finally:
        sys.argv = saved_argv
    predicted = section["predicted_s"]
    measured = section["measured_s"]
    return {
        "transfers": len(section["transfers"]),
        "bytes_on_wire": int(sum(section["bytes_on_wire"].values())),
        "predicted_s": round(predicted, 6),
        "measured_s": round(measured, 6),
        # >1 = the plan is optimistic on this backend (XLA:CPU pays
        # dispatch per leaf); the re-planner consumes this ratio as its
        # calibration factor
        "measured_vs_predicted": (round(measured / predicted, 4)
                                  if predicted > 0 else None),
        "stage3_src": True,
        "errors": (section.get("analysis") or {}).get("errors"),
    }


def _elastic_legs(cfg, on_tpu: bool) -> dict:
    """ffelastic leg: the cost of staying live through a re-plan
    (elastic/, docs/elastic.md). One dp=4 LM takes an injected 50x
    drift perturbation mid-fit; the leg records how long the loop ran
    on the stale plan (trigger latency), what the online re-search
    cost, what the migration cost vs its fftrans prediction (the
    fidelity ratio the payoff rule calibrates from), and how many
    steps until the drift monitor read clean again (steps-to-recover)."""
    import tempfile

    import jax

    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models import build_transformer_lm

    n_dev = jax.device_count()
    if n_dev < 4:
        return {"skipped": f"{n_dev} device(s) — no dp=4 elastic leg"}

    saved_argv = list(sys.argv)
    tdir = tempfile.mkdtemp(prefix="bench_elastic_")
    try:
        sys.argv = [sys.argv[0], "--telemetry-dir", tdir, "--diagnostics"]
        config = FFConfig()
        config.mesh_axis_sizes = (4, 1, 1, 1)
        config.batch_size = 4
        ff = FFModel(config)
        build_transformer_lm(ff, cfg, batch_size=4)
        ff.compile(optimizer=SGDOptimizer(lr=0.01, momentum=0.9),
                   loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)
        rs = np.random.RandomState(0)
        n = 24  # 6 steps/epoch
        X = {"tokens": rs.randint(
                0, cfg.vocab_size,
                (n, cfg.sequence_length)).astype(np.int32),
             "positions": np.tile(
                 np.arange(cfg.sequence_length, dtype=np.int32), (n, 1))}
        Y = rs.randint(0, cfg.vocab_size,
                       (n, cfg.sequence_length, 1)).astype(np.int32)
        ff.fit(X, Y, epochs=1, batch_size=4, shuffle=False, verbose=False)

        ctrl = ff.enable_elastic(
            cooldown_steps=0, horizon_steps=1000,
            visible_devices_fn=lambda: jax.devices()[:4])
        diag = ff.get_diagnostics()
        # the injected perturbation: the monitor now reads every step
        # as a 50x excursion over the plan's claimed makespan
        diag.drift.set_prediction((ff._predicted_step_s or 1e-3) / 50)

        step_times = []  # (step, device_time_s) during the elastic fit
        orig_on_step = diag.on_step

        def probe(rec):
            orig_on_step(rec)
            if ctrl.decisions:
                # freeze after the first decision: the recovery window
                # must not be polluted by a second re-plan
                ctrl.cooldown_steps = 10_000
            dev = rec.get("device_time_s")
            if dev is not None:
                step_times.append((int(rec.get("step", 0)), float(dev)))

        diag.on_step = probe
        ff.fit(X, Y, epochs=2, batch_size=4, shuffle=False, verbose=False)
    finally:
        sys.argv = saved_argv

    drifts = [d for d in ctrl.decisions if d.get("trigger") == "drift"]
    if not drifts:
        return {"skipped": "no drift decision fired", "decisions": 0}
    d0 = drifts[0]
    # steps-to-recover: first post-decision step whose device time is
    # back within 2x the pre-decision norm (the re-plan step itself
    # carries the recompile+migration spike)
    dstep = int(d0["step"])
    pre = sorted(t for s, t in step_times if s <= dstep)
    norm = pre[len(pre) // 2] if pre else None
    rec_step = next((s for s, t in step_times
                     if s > dstep and norm and t <= 2 * norm), None)
    pred = d0.get("predicted_migration_s")
    meas = d0.get("migration_measured_s")
    return {
        "decision": d0.get("decision"),
        "decisions": len(ctrl.decisions),
        # steps the loop ran on the stale plan between the advisory and
        # the decision (the controller consumes at the next boundary)
        "trigger_latency_steps": int(d0["step"])
        - int(d0["advisory"]["step"]),
        "research_s": round(d0.get("research_s") or 0.0, 6),
        "migration_predicted_s": (None if pred is None
                                  else round(pred, 6)),
        "migration_measured_s": (None if meas is None
                                 else round(meas, 6)),
        "migration_measured_vs_predicted": (
            round(meas / pred, 4)
            if pred and meas and pred > 0 else None),
        "steps_to_recover": (rec_step - dstep
                             if rec_step is not None else None),
        "lhs_s": d0.get("lhs_s"),
        "rhs_s": d0.get("rhs_s"),
    }


def _serving_legs(cfg, on_tpu: bool) -> dict:
    """Serving legs: requests/s/chip + decode tokens/s/chip through the
    continuous-batching engine (serving/) — the ROADMAP's "millions of
    users" metric next to the training slope — plus the PAGED-KV
    shared-prefix leg (`serving.paged` in the BENCH payload): the same
    engine re-run on a trace where every prompt opens with one system
    prompt, reporting prefix_hit_rate, cow_copies, and
    slots_at_fixed_hbm (contiguous KV rows ÷ the pool's peak working
    set — the vLLM capacity-recovery metric; ISSUE 11's bar is >= 2x).
    Completions are asserted bit-identical across layouts. The decode
    executables are warmed by one throwaway request so each measured
    drain is steady-state continuous batching. scripts/serve_bench.py is
    the standalone, load-tunable twin."""
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu import telemetry
    from flexflow_tpu.models import TransformerLMConfig, build_transformer_lm

    if on_tpu:
        n_requests, slots, prompt_len, max_new = 32, 8, 8, 16
        shared_prefix, block = 64, 16
        sp_prompt_len = 96
    else:
        cfg = TransformerLMConfig(
            vocab_size=256, hidden_size=64, num_heads=2, num_layers=1,
            sequence_length=64, attention_impl="xla")
        n_requests, slots, prompt_len, max_new = 8, 4, 8, 8
        shared_prefix, block = 9, 4
        sp_prompt_len = 12
    config = FFConfig()
    config.batch_size = slots
    if on_tpu:
        from flexflow_tpu.fftype import DataType

        config.computation_dtype = DataType.DT_BFLOAT16
    ff = FFModel(config)
    build_transformer_lm(ff, cfg, batch_size=slots)
    with telemetry.span("bench.serve.compile"):
        ff.compile(optimizer=SGDOptimizer(lr=0.01),
                   loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)

    def drain(engine, prompts, tag):
        with telemetry.span("bench.serve.warmup", leg=tag):
            engine.generate(prompts[:1])  # compile buckets + decode step
        engine.reset_stats()
        for p in prompts:
            engine.submit(p)
        with telemetry.span("bench.serve.measure", leg=tag,
                            requests=len(prompts)):
            engine.run_until_drained()
        return ([r.generated for r in engine.scheduler.completed],
                engine.metrics_summary())

    rs = np.random.RandomState(0)
    prompts = [rs.randint(1, cfg.vocab_size, prompt_len).tolist()
               for _ in range(n_requests)]
    engine = ff.serve(slots=slots, max_new_tokens=max_new, prefill_chunk=8)
    _, stats = drain(engine, prompts, "uniform")
    out = {
        "requests_per_sec_per_chip": round(
            stats.get("requests_per_sec_per_chip", 0.0), 4),
        "decode_tokens_per_sec_per_chip": round(
            stats.get("decode_tokens_per_sec_per_chip", 0.0), 2),
        "requests": stats["requests_completed"],
        "slots": slots,
        "max_new_tokens": max_new,
        "kv_layout": stats["kv_layout"],
        "ttft_p50_s": round(stats.get("ttft_p50_s", 0.0), 4),
        # drain-count accounting: prompts that finished without emitting
        # a token are excluded from the TTFT denominator by design
        "no_token_requests": stats.get("no_token_requests", 0),
    }
    # request-grain tail latency from the engine's mergeable histograms
    # (engine.metrics_summary) — present whenever the measured window
    # saw the observation
    for short in ("queue_wait", "ttft", "tbt", "e2e"):
        for q in ("p50", "p95", "p99"):
            key = f"{short}_{q}_s"
            if key in stats:
                out[key] = round(stats[key], 6)

    # paged shared-prefix leg vs the contiguous ablation on one trace
    system = rs.randint(1, cfg.vocab_size, shared_prefix).tolist()
    tail = max(1, sp_prompt_len - shared_prefix)
    sp = [system + rs.randint(1, cfg.vocab_size, tail).tolist()
          if i else list(system) for i in range(n_requests)]
    paged_eng = ff.serve(slots=slots, max_new_tokens=max_new,
                         prefill_chunk=8, kv_layout="paged",
                         kv_block_size=block)
    paged_out, pst = drain(paged_eng, sp, "shared-prefix-paged")
    contig_eng = ff.serve(slots=slots, max_new_tokens=max_new,
                          prefill_chunk=8, kv_layout="contiguous")
    contig_out, cst = drain(contig_eng, sp, "shared-prefix-contiguous")
    if paged_out != contig_out:
        raise AssertionError(
            "paged completions diverge from contiguous on the "
            "shared-prefix trace")
    out["paged"] = {
        "shared_prefix": shared_prefix,
        "kv_block_size": pst["kv_block_size"],
        "requests_per_sec_per_chip": round(
            pst.get("requests_per_sec_per_chip", 0.0), 4),
        "contiguous_requests_per_sec_per_chip": round(
            cst.get("requests_per_sec_per_chip", 0.0), 4),
        "prefix_hit_rate": round(pst.get("prefix_hit_rate", 0.0), 4),
        "cow_copies": pst.get("cow_copies", 0),
        "kv_blocks_in_use_peak": pst.get("kv_blocks_in_use_peak", 0),
        "kv_hbm_bytes_per_layer": pst.get("kv_hbm_bytes_per_layer", 0),
        "contiguous_kv_hbm_bytes_per_layer": cst.get(
            "kv_hbm_bytes_per_layer", 0),
        # the engine's one definition of the capacity-recovery ratio
        # (serving/engine.py stats() `kv_peak_vs_contiguous`)
        "slots_at_fixed_hbm": round(pst["kv_peak_vs_contiguous"], 4),
    }

    # disaggregated leg (`serving.disagg` in the BENCH payload): the
    # same shared-prefix trace through serve(disaggregate=True) — two
    # Unity plans on disjoint sub-meshes at EQUAL total chips — next to
    # the unified paged engine above: TTFT/TBT p50/p95 side by side,
    # every KV handoff's measured-vs-predicted seconds, and the
    # decode-side radix hit rate on a SECOND wave after a full drain
    # with and without the cross-time cache (prefix_cache=False is the
    # ablation: prefixes die with their last resident). Needs >= 2
    # devices to split; a 1-chip run records why it skipped.
    import jax

    if jax.device_count() >= 2:
        try:
            out["disagg"] = _disagg_serving_leg(
                ff, telemetry, sp, slots, max_new, block,
                sorted(paged_eng.scheduler.completed,
                       key=lambda r: r.request_id), pst)
        except Exception as e:
            out["disagg"] = {"skipped": f"{type(e).__name__}: {e}"}
    else:
        out["disagg"] = {"skipped": "single device — no chips to split"}

    # speculative leg (`serving.spec` in the BENCH payload): the same
    # shared-prefix trace through serve(speculate=True, draft_model=...)
    # with a seed-clone drafter (the all-accept extreme — the verify-path
    # ceiling on untrained weights), colocated so no extra chips are
    # consumed: TBT p50/p95 + decode tokens/s/chip next to the unified
    # paged engine, plus the acceptance rate and the payoff gate's
    # decision tally. Bit-identity to the unified drain is asserted —
    # speculation is a latency optimization, never a sampling change.
    try:
        out["spec"] = _spec_serving_leg(
            ff, cfg, telemetry, sp, slots, max_new, block,
            sorted(paged_eng.scheduler.completed,
                   key=lambda r: r.request_id), pst)
    except Exception as e:
        out["spec"] = {"skipped": f"{type(e).__name__}: {e}"}
    return out


def _spec_serving_leg(ff, lm_cfg, telemetry, prompts, slots, max_new,
                      block, unified_done, unified_stats) -> dict:
    """One `serving.spec` payload: the shared-prefix trace through the
    speculative engine (seed-clone drafter, colocated), asserted
    bit-identical to the unified paged drain (`unified_done`, sorted by
    request id)."""
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models import build_transformer_lm

    dconfig = FFConfig()
    dconfig.batch_size = slots
    draft = FFModel(dconfig)
    build_transformer_lm(draft, lm_cfg, batch_size=slots)
    with telemetry.span("bench.serve.compile", leg="spec-drafter"):
        draft.compile(
            optimizer=SGDOptimizer(lr=0.01),
            loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY)

    eng = ff.serve(speculate=True, draft_model=draft, slots=slots,
                   max_new_tokens=max_new, prefill_chunk=8,
                   kv_block_size=block)
    with telemetry.span("bench.serve.warmup", leg="spec"):
        # full-trace warmup: compiles the decode buckets AND the
        # drafter/verify executables, and warms the acceptance EMA so
        # the measured wave runs on a calibrated payoff gate
        eng.generate(prompts)
    eng.reset_stats()
    for p in prompts:
        eng.submit(p)
    with telemetry.span("bench.serve.measure", leg="spec",
                        requests=len(prompts)):
        eng.run_until_drained()
    done = sorted(eng.scheduler.completed, key=lambda r: r.request_id)
    if [r.generated for r in done] != [r.generated for r in unified_done]:
        raise AssertionError(
            "speculative completions diverge from the unified paged "
            "engine on the shared-prefix trace")
    st = eng.metrics_summary()
    sp = eng.stats()["speculation"]
    leg = {
        "draft_chips": eng.draft_chips,
        "k_max": eng.k_max,
        "rounds": sp["rounds"],
        "acceptance_rate": round(sp["acceptance_rate"], 4),
        "acceptance_ema": round(sp["acceptance_ema"], 4),
        "decision_counts": sp["decision_counts"],
        "requests": len(prompts),
        "decode_tokens_per_sec_per_chip": round(
            st.get("decode_tokens_per_sec_per_chip", 0.0), 2),
        "unified_decode_tokens_per_sec_per_chip": round(
            unified_stats.get("decode_tokens_per_sec_per_chip", 0.0), 2),
    }
    for q in ("p50", "p95"):
        key = f"tbt_{q}_s"
        if key in st:
            leg[key] = round(st[key], 6)
        if key in unified_stats:
            leg[f"unified_{key}"] = round(unified_stats[key], 6)
    return leg


def _disagg_serving_leg(ff, telemetry, prompts, slots, max_new, block,
                        unified_done, unified_stats) -> dict:
    """One `serving.disagg` payload: the shared-prefix trace through the
    disaggregated engine, asserted bit-identical to the unified paged
    drain (`unified_done`, sorted by request id), with the cross-time
    radix ablation run on a separate prefix_cache=False engine."""

    def wave(engine, tag):
        engine.reset_stats()
        for p in prompts:
            engine.submit(p)
        with telemetry.span("bench.serve.measure", leg=tag,
                            requests=len(prompts)):
            engine.run_until_drained()
        done = sorted(engine.completed, key=lambda r: r.request_id)
        return [r.generated for r in done], engine.metrics_summary()

    dis = ff.serve(disaggregate=True, slots=slots, max_new_tokens=max_new,
                   prefill_chunk=8, kv_block_size=block)
    with telemetry.span("bench.serve.warmup", leg="disagg"):
        dis.generate(prompts[:1])
    done, dst = wave(dis, "disagg")
    if done != [r.generated for r in unified_done]:
        raise AssertionError(
            "disaggregated completions diverge from the unified paged "
            "engine on the shared-prefix trace")
    fully_cached = sum(1 for h in dis.handoffs
                       if h["injected_blocks"] == 0)
    # second wave AFTER the full drain: every hit here crossed a drain
    # boundary, i.e. came from the cross-time radix cache
    _, dst2 = wave(dis, "disagg-wave2")
    fully_cached += sum(1 for h in dis.handoffs
                        if h["injected_blocks"] == 0)

    # ablation: same engine shape, prefix_cache=False — the registry
    # dies with its residents, so wave 2 restarts cold
    nc = ff.serve(disaggregate=True, slots=slots, max_new_tokens=max_new,
                  prefill_chunk=8, kv_block_size=block, prefix_cache=False)
    with telemetry.span("bench.serve.warmup", leg="disagg-nocache"):
        nc.generate(prompts[:1])
    nc_done, _ = wave(nc, "disagg-nocache")
    if nc_done != done:
        raise AssertionError(
            "prefix_cache=False completions diverge — the cross-time "
            "cache changed tokens")
    _, nst2 = wave(nc, "disagg-nocache-wave2")

    leg = {
        "prefill_chips": dis.prefill_chips,
        "decode_chips": dis.decode_chips,
        "kv_block_size": block,
        "requests": len(prompts),
        "requests_per_sec_per_chip": round(
            dst.get("requests_per_sec_per_chip", 0.0), 4),
        "unified_requests_per_sec_per_chip":
            unified_stats.get("requests_per_sec_per_chip", 0.0),
        # handoff plane: measured wall next to the fftrans prediction,
        # summed over the measured wave (disagg_section carries the
        # per-handoff records + verified programs in the strategy report)
        "handoffs": dst.get("handoffs", 0) + dst2.get("handoffs", 0),
        "fully_cached_handoffs": fully_cached,
        "handoff_predicted_s": round(dst2.get("handoff_predicted_s", 0.0)
                                     + dst.get("handoff_predicted_s", 0.0),
                                     6),
        "handoff_measured_s": round(dst2.get("handoff_measured_s", 0.0)
                                    + dst.get("handoff_measured_s", 0.0),
                                    6),
        # post-drain wave hit rates: with the cross-time radix cache vs
        # the prefix_cache=False ablation at identical load
        "prefix_hit_rate_cross_time": round(
            (dst2.get("decode") or {}).get("prefix_hit_rate", 0.0), 4),
        "prefix_hit_rate_no_cross_time": round(
            (nst2.get("decode") or {}).get("prefix_hit_rate", 0.0), 4),
    }
    # TTFT observes on the prefill side, TBT on the decode side; the
    # unified engine's flat keys sit next to them for the equal-chips
    # comparison
    pre, dec = dst.get("prefill") or {}, dst.get("decode") or {}
    for short, side in (("ttft", pre), ("queue_wait", pre), ("tbt", dec)):
        for q in ("p50", "p95"):
            key = f"{short}_{q}_s"
            if key in side:
                leg[key] = round(side[key], 6)
            if key in unified_stats:
                leg[f"unified_{key}"] = round(unified_stats[key], 6)
    return leg


def main():
    # --telemetry-dir DIR: archive this run's host-side timeline + metrics
    # (trace.json / metrics.jsonl) so BENCH numbers come with forensics.
    # Parsed here because the harness deliberately clears argv below (the
    # model under test must not inherit bench flags).
    argv = sys.argv[1:]
    telemetry_dir = None
    if "--telemetry-dir" in argv:
        i = argv.index("--telemetry-dir")
        if i + 1 >= len(argv) or argv[i + 1].startswith("--"):
            print("bench: --telemetry-dir requires a directory argument",
                  file=sys.stderr)
            sys.exit(2)
        telemetry_dir = argv[i + 1]
    sys.argv = [sys.argv[0]]
    # the compile cache: where JAX_COMPILATION_CACHE_DIR says, else one
    # fixed place in the checkout (a path that moves never hits)
    import os

    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".jax_cache"))
    import jax

    from flexflow_tpu import telemetry
    from flexflow_tpu.models import TransformerLMConfig

    session = None
    if telemetry_dir:
        session = telemetry.activate(telemetry.TelemetrySession(telemetry_dir))
        session.write_manifest()
    try:
        _bench_body(jax, TransformerLMConfig, telemetry, session)
    finally:
        # the timeline must survive a mid-bench crash — that is exactly
        # when the archived trace is wanted (close() is idempotent; the
        # success path already closed with the bench event recorded)
        if session is not None:
            session.close()


def _bench_body(jax, TransformerLMConfig, telemetry, session):
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if on_tpu:
        cfg = TransformerLMConfig(
            vocab_size=32000, hidden_size=1024, num_heads=16, num_layers=12,
            sequence_length=512, attention_impl="flash",
        )
        batch = 8
        steps, warmup = 20, 3
    else:  # CPU smoke mode
        cfg = TransformerLMConfig(
            vocab_size=512, hidden_size=128, num_heads=4, num_layers=2,
            sequence_length=128, attention_impl="xla",
        )
        batch = 4
        steps, warmup = 5, 1

    primary_mem: dict = {}
    tokens_per_sec, mfu = _measure_lm(cfg, batch, steps, warmup, on_tpu,
                                      out=primary_mem)

    seq4096 = None
    if on_tpu and tokens_per_sec is not None:
        # secondary LONG-CONTEXT leg (seq 4096, same model family): the
        # regime where flash's causal block-skipping and the online-softmax
        # path actually matter — quantifies the exceeds-reference
        # long-context capability (SURVEY §5). Printed BEFORE the primary
        # line (the driver's number of record is the LAST line — r05's
        # record was accidentally this leg, a phantom 41% regression);
        # failures only print to stderr.
        try:
            lcfg = TransformerLMConfig(
                vocab_size=32000, hidden_size=1024, num_heads=16,
                num_layers=12, sequence_length=4096,
                attention_impl="flash",
            )
            tps4k, mfu4k = _measure_lm(lcfg, batch=1, steps=5, warmup=1,
                                       on_tpu=on_tpu)
            if tps4k is not None:
                seq4096 = {
                    "metric": "transformer_lm_tokens_per_sec_per_chip_seq4096",
                    "value": round(tps4k, 2),
                    "unit": "tokens/s",
                    "vs_baseline": round(mfu4k / 0.35, 4),
                }
                # attention-ablation legs (round 7): ring overlap on/off
                try:
                    seq4096["ablation"] = _attention_ablation_legs(
                        lcfg, batch=1, steps=5, warmup=1, on_tpu=on_tpu)
                except Exception as e:  # pragma: no cover - defensive
                    print(f"bench: attention ablation failed: {e}",
                          file=sys.stderr)
                print(json.dumps(seq4096))
            else:
                print("bench: long-context leg read as fluke, skipped",
                      file=sys.stderr)
        except Exception as e:  # pragma: no cover - defensive
            print(f"bench: long-context leg failed: {e}", file=sys.stderr)

    # fit-loop legs (eager vs --pipeline-steps): the throughput training
    # jobs actually see, printed as secondary lines AND archived inside
    # the primary payload so the bench-vs-fit gap is tracked per round
    fit_loop = None
    try:
        fit_loop = _fit_loop_legs(cfg, batch, on_tpu)
        print(json.dumps({
            "metric": "transformer_lm_fit_tokens_per_sec_eager",
            "value": fit_loop["eager_tokens_per_sec"],
            "unit": "tokens/s",
        }))
        print(json.dumps({
            "metric": "transformer_lm_fit_tokens_per_sec_pipelined",
            "value": fit_loop["pipelined_tokens_per_sec"],
            "pipeline_steps": fit_loop["pipeline_steps"],
            "speedup_vs_eager_fit": fit_loop["speedup"],
            "unit": "tokens/s",
        }))
    except Exception as e:  # pragma: no cover - defensive
        print(f"bench: fit-loop leg failed: {e}", file=sys.stderr)

    # grad-sync ablation legs (round 8): replicated allreduce vs ZeRO-
    # sharded update with/without overlap, with per-leg resident HBM so
    # the 1/dp optimizer-state saving lands next to tokens/s/chip
    grad_sync = None
    try:
        grad_sync = _grad_sync_legs(cfg, batch, steps, warmup, on_tpu)
        print(json.dumps({
            "metric": "grad_sync_ablation",
            **{k: v for k, v in grad_sync.items() if k != "rs_microbench"},
        }))
    except Exception as e:  # pragma: no cover - defensive
        print(f"bench: grad-sync ablation failed: {e}", file=sys.stderr)

    # param-sharding ablation legs (ZeRO-3/FSDP): replicated vs stage-2
    # vs stage-3 with addressable param bytes/chip at rest, peak HBM and
    # step time
    param_sharding = None
    try:
        param_sharding = _param_sharding_legs(cfg, batch, steps, warmup,
                                              on_tpu)
        print(json.dumps({"metric": "param_sharding_ablation",
                          **param_sharding}))
    except Exception as e:  # pragma: no cover - defensive
        print(f"bench: param-sharding ablation failed: {e}",
              file=sys.stderr)

    # serving leg: requests/s/chip + decode tokens/s/chip through the
    # continuous-batching engine, as secondary lines + a `serving` field
    # in the primary payload
    serving = None
    try:
        serving = _serving_legs(cfg, on_tpu)
        print(json.dumps({
            "metric": "serving_requests_per_sec_per_chip",
            "value": serving["requests_per_sec_per_chip"],
            "unit": "req/s",
        }))
        print(json.dumps({
            "metric": "serving_decode_tokens_per_sec_per_chip",
            "value": serving["decode_tokens_per_sec_per_chip"],
            "unit": "tokens/s",
        }))
        if "paged" in serving:
            print(json.dumps({
                "metric": "serving_paged_slots_at_fixed_hbm",
                "value": serving["paged"]["slots_at_fixed_hbm"],
                "prefix_hit_rate": serving["paged"]["prefix_hit_rate"],
                "unit": "x contiguous",
            }))
        dg = serving.get("disagg") or {}
        if "prefill_chips" in dg:
            # the disaggregation headline: TTFT p95 at equal total chips
            # vs the unified engine, and the cross-time radix ablation
            print(json.dumps({
                "metric": "serving_disagg_ttft_p95_s",
                "value": dg.get("ttft_p95_s"),
                "unified_ttft_p95_s": dg.get("unified_ttft_p95_s"),
                "chips": f"{dg['prefill_chips']}p+{dg['decode_chips']}d",
                "unit": "s",
            }))
            print(json.dumps({
                "metric": "serving_disagg_prefix_hit_rate_cross_time",
                "value": dg.get("prefix_hit_rate_cross_time"),
                "no_cross_time": dg.get("prefix_hit_rate_no_cross_time"),
            }))
        sg = serving.get("spec") or {}
        if "rounds" in sg:
            # the speculation headline: TBT p95 vs plain decode at the
            # same chips, with the acceptance rate that priced the gate
            print(json.dumps({
                "metric": "serving_spec_tbt_p95_s",
                "value": sg.get("tbt_p95_s"),
                "unified_tbt_p95_s": sg.get("unified_tbt_p95_s"),
                "acceptance_rate": sg.get("acceptance_rate"),
                "rounds": sg.get("rounds"),
                "unit": "s",
            }))
    except Exception as e:  # pragma: no cover - defensive
        print(f"bench: serving leg failed: {e}", file=sys.stderr)

    # migration leg (fftrans): measured in-process migration seconds vs
    # the TransitionPlan's prediction on this mesh — the cost-model
    # fidelity datapoint the re-planner's pay-off rule will consume
    migration = None
    try:
        migration = _migration_legs(cfg, on_tpu)
        print(json.dumps({
            "metric": "migration_seconds",
            **{k: migration[k] for k in
               ("predicted_s", "measured_s", "measured_vs_predicted",
                "transfers", "bytes_on_wire")
               if k in migration},
            "unit": "s",
        }))
    except Exception as e:  # pragma: no cover - defensive
        print(f"bench: migration leg failed: {e}", file=sys.stderr)

    # elastic leg (ffelastic): one injected-drift live re-plan — trigger
    # latency, online re-search seconds, migration measured vs
    # predicted, and steps-to-recover, as a secondary line + an
    # `elastic` field in the primary payload
    elastic = None
    try:
        elastic = _elastic_legs(cfg, on_tpu)
        print(json.dumps({
            "metric": "elastic_replan",
            **{k: elastic[k] for k in
               ("decision", "trigger_latency_steps", "research_s",
                "migration_predicted_s", "migration_measured_s",
                "migration_measured_vs_predicted", "steps_to_recover",
                "skipped")
               if k in elastic},
            "unit": "s",
        }))
    except Exception as e:  # pragma: no cover - defensive
        print(f"bench: elastic leg failed: {e}", file=sys.stderr)

    # rule-registry leg (ffrules, BENCH hygiene): pin the substitution
    # rule set the plans in this capture were searched under — the
    # content fingerprint (the component that joins the warm-start plan
    # address) plus the full five-pass verification wall time, so the
    # next driver capture can tell "rules changed" from "cost model
    # drifted" when a searched plan moves
    rules_leg = None
    try:
        rules_leg = _rules_leg()
        print(json.dumps({
            "metric": "rules_verify_wall_s",
            "value": rules_leg["verify_wall_s"],
            "rules": rules_leg["rules"],
            "fingerprint": rules_leg["fingerprint"][:16],
            "unit": "s",
        }))
    except Exception as e:  # pragma: no cover - defensive
        # the failure itself is recorded in the payload: a capture whose
        # registry failed verification (or could not be fingerprinted)
        # must never read as a clean capture
        rules_leg = {"error": f"{type(e).__name__}: {e}"}
        print(f"bench: rules leg failed: {e}", file=sys.stderr)

    # warm-start legs: cold-vs-warm time-to-first-step against one shared
    # --warmstart-dir (secondary line + archived in the primary payload)
    warmstart = None
    try:
        warmstart = _warmstart_legs()
        print(json.dumps({
            "metric": "warmstart_time_to_first_step_s",
            "cold": warmstart["cold_time_to_first_step_s"],
            "warm": warmstart["warm_time_to_first_step_s"],
            "speedup": warmstart["speedup"],
            "unit": "s",
        }))
    except Exception as e:  # pragma: no cover - defensive
        print(f"bench: warm-start leg failed: {e}", file=sys.stderr)

    # one payload feeds both the archived metrics record and the printed
    # line of record — they must never drift apart
    payload = {
        "metric": "transformer_lm_tokens_per_sec_per_chip",
        "value": None if tokens_per_sec is None else round(tokens_per_sec, 2),
        "unit": "tokens/s",
        "vs_baseline": None if tokens_per_sec is None else round(mfu / 0.35, 4),
        # allocator peak of the primary leg (null where the backend has no
        # memory_stats, e.g. XLA:CPU): the reading the 1/dp optimizer-
        # state saving moves — compare against grad_sync's per-leg
        # resident bytes
        "peak_hbm_bytes_per_chip": primary_mem.get("peak_hbm_bytes"),
    }
    if seq4096 is not None:
        payload["seq4096"] = seq4096
    if fit_loop is not None:
        payload["fit_loop"] = fit_loop
    if grad_sync is not None:
        payload["grad_sync"] = grad_sync
    if param_sharding is not None:
        payload["param_sharding"] = param_sharding
    if serving is not None:
        payload["serving"] = serving
    if migration is not None:
        payload["migration"] = migration
    if elastic is not None:
        payload["elastic"] = elastic
    if warmstart is not None:
        payload["warmstart"] = warmstart
    if rules_leg is not None:
        payload["rules"] = rules_leg
    if tokens_per_sec is None:
        # a physically impossible reading must never become the number of
        # record: emit null and fail so the driver records the fluke as a
        # fluke instead of a result
        print("bench: all retries read >100% MFU — backend measurement "
              "fluke, result is NOT trustworthy", file=sys.stderr)
        print(json.dumps(payload))
        if session is not None:
            telemetry.event("bench", fluke=True, **payload)
            session.close()
        sys.exit(1)
    if session is not None:
        telemetry.event("bench", **payload)
        session.close()
    # primary metric LAST — the driver parses the last line as the number
    # of record
    print(json.dumps(payload))
    sys.stdout.flush()


if __name__ == "__main__":
    main()
