"""FFConfig: runtime configuration + CLI flag parsing.

Parity with the reference's hand-rolled argv scan
(include/flexflow/config.h:92-160, src/runtime/model.cc:3500-3720): the same
flags are accepted (`-b`, `--epochs`, `-e`, `--budget`, `--alpha`,
`--only-data-parallel`, `--enable-parameter-parallel`, ...), plus TPU-native
knobs (mesh axis sizes, bf16 policy). Legion `-ll:gpu/-ll:cpu` flags map to
workers-per-node over the JAX device fleet.
"""

from __future__ import annotations

import os
import sys
from dataclasses import dataclass, field
from typing import Optional

import jax

from .fftype import CompMode, DataType
from .machine import DEFAULT_AXES, MeshShape

# Flags parsed for reference-CLI parity whose mechanics have no TPU analog;
# passing them warns loudly instead of silently doing nothing.
# (--search-overlap-backward-update is NOT here: it switches the cost
# model's gradient-sync overlap semantics, cost_model._MakespanAccum.)
_PARITY_ONLY_FLAGS = frozenset({
    "--simulator-workspace-size", "--segment-size", "--max-num-segments",
    "--enable-propagation",
})


@dataclass
class FFConfig:
    # training loop
    epochs: int = 1
    batch_size: int = 64
    print_freq: int = 10
    learning_rate: float = 0.01
    weight_decay: float = 0.0001
    # fleet description
    num_nodes: int = 1
    cpus_per_node: int = 4
    workers_per_node: int = 0  # 0 → all local devices
    device_mem: float = 0.0  # bytes of HBM per chip (0 → query)
    # search
    search_budget: int = 0
    search_alpha: float = 1.2
    search_overlap_backward_update: bool = False
    simulator_work_space_size: int = 2 * 1024 * 1024 * 1024
    search_num_nodes: Optional[int] = None
    search_num_workers: Optional[int] = None
    base_optimize_threshold: int = 10
    enable_propagation: bool = False
    perform_memory_search: bool = False
    # on-device cost-model calibration: measure the top-K distinct ops on
    # the local chip before searching (measure_operator_cost analog); 0=off
    search_calibrate: int = 0
    # also search over mesh factorizations of the chip count (the
    # MachineView grid-shape half of Unity — divisor degrees are reached by
    # re-factorizing the mesh, search/mesh_search.py); the searched shape
    # replaces the configured data/model split
    search_mesh_shapes: bool = False
    # overlap-capable collectives (ring attention's double-buffered
    # ppermute pipeline, the decomposed collective matmul): True prices
    # and schedules them overlapped with compute — max(compute, comm) in
    # the cost model, hop-before-compute in the runtime; False restores
    # the serial compute+comm pricing and schedule (the ablation
    # baseline, bench.py's ring legs)
    overlap_collectives: bool = True
    # weight-update sharding (ZeRO / Xu et al. 2020; FSDP, Zhao et al.
    # 2023): fp32 masters + optimizer slots sharded 1/dp along the
    # gradient-reduction axes (stage 2), and — stage 3 — the trainable
    # weights themselves sharded at rest and all-gathered per layer where
    # the layer uses them (XLA's collective, once a step; the backward
    # reads the gathered copy). None (default) = Unity decides by pricing
    # replicated vs stage 2 vs stage 3 — sharded is selected exactly
    # when the plan is memory- or grad-sync-bound, and stage 3 exactly
    # when stage 2's resident gathered copies are themselves over the
    # HBM cap (search/unity.choose_update_sharding).
    # `--weight-update-sharding[=stage3|stage2|off|on]` /
    # `--no-weight-update-sharding` force it (weight_update_stage: None
    # = auto among the enabled stages, 0/2/3 = forced). Bit-identical
    # trajectories at every stage (docs/performance.md).
    weight_update_sharding: Optional[bool] = None
    weight_update_stage: Optional[int] = None
    # parallelism gates (reference config.h:133-137)
    only_data_parallel: bool = False
    enable_sample_parallel: bool = False
    enable_parameter_parallel: bool = False
    enable_attribute_parallel: bool = False
    enable_inplace_optimizations: bool = False
    enable_control_replication: bool = True
    # substitution search: explore GraphXfer-rewritten PCGs (inserting
    # Repartition/Combine/Replicate/Reduction nodes) instead of only
    # assigning configs on the fixed graph; implied by --substitution-json
    enable_substitutions: bool = False
    # execution
    computation_mode: CompMode = CompMode.COMP_MODE_TRAINING
    profiling: bool = False
    perform_fusion: bool = False
    synthetic_input: bool = False
    # Mixed precision. allow_tensor_op_math_conversion is the reference's
    # cublas tensor-op flag recast for the MXU: fp32 matmul *inputs* are cast
    # to bf16 with fp32 accumulation (applies on TPU; force_tensor_op_math
    # extends it to CPU for tests). computation_dtype=DT_BFLOAT16 is the full
    # policy: bf16 activations end-to-end with fp32 master weights, optimizer
    # state, loss, and normalization statistics.
    allow_tensor_op_math_conversion: bool = True
    force_tensor_op_math: bool = False
    computation_dtype: Optional[DataType] = None  # None → fp32 activations
    # files / misc
    dataset_path: str = ""
    import_strategy_file: str = ""
    export_strategy_file: str = ""
    export_strategy_task_graph_file: str = ""
    export_strategy_computation_graph_file: str = ""
    substitution_json_path: Optional[str] = None
    machine_model_version: int = 0
    machine_model_file: str = ""
    simulator_segment_size: int = 16777216
    simulator_max_num_segments: int = 1
    python_data_loader_type: int = 2
    # TPU-native additions
    mesh_axis_sizes: Optional[tuple[int, ...]] = None  # (data, model, pipe, seq)
    mesh_axis_names: tuple[str, ...] = DEFAULT_AXES
    seed: int = 0
    # resilience (resilience/): async checkpointing + preemption-safe fit.
    # checkpoint_dir enables the subsystem; every-N-steps / every-T-seconds
    # gate the async saves; auto_resume restores the newest committed
    # checkpoint (resharding onto this run's mesh) before training.
    checkpoint_dir: str = ""
    checkpoint_every: int = 0
    checkpoint_every_seconds: float = 0.0
    checkpoint_keep: int = 3
    auto_resume: bool = False
    # observability (telemetry/): telemetry_dir enables the run-wide
    # tracer + JSONL metrics log (trace.json / metrics.jsonl under the
    # dir); xprof_dir additionally wraps fit in jax.profiler.trace for
    # device-level XProf timelines (docs/observability.md)
    telemetry_dir: str = ""
    xprof_dir: str = ""
    # ffpulse continuous export (telemetry/export.py, needs telemetry):
    # metrics_interval > 0 writes a rolling metrics_snapshot record +
    # metrics.prom every N seconds; metrics_port serves the latest
    # snapshot at /metrics and liveness at /healthz on 127.0.0.1
    # (coordinator-only; port 0 = off)
    metrics_interval: float = 0.0
    metrics_port: int = 0
    # diagnostics (diagnostics/): strategy explain report at compile,
    # online cost-model drift monitoring and run-health anomaly rules
    # during fit. Requires telemetry (the artifacts live in its dir).
    # drift_threshold is the EMA of |measured − predicted| / predicted
    # device step time above which a costmodel.drift advisory fires;
    # health_abort_on lists rule names ("nan_loss", "step_spike",
    # "data_wait_stall", "ckpt_stale") whose alerts abort training instead
    # of warning.
    diagnostics: bool = False
    drift_threshold: float = 0.5
    health_abort_on: tuple[str, ...] = ()
    # elastic re-planning (elastic/): the controller consumes drift
    # advisories and visible-device capacity deltas during fit (and the
    # serving step loop), re-searches online, and migrates in-process
    # when predicted_migration_s × fidelity < benefit/step × horizon.
    # cooldown spaces consecutive re-plan attempts (a capacity shrink
    # bypasses it); horizon is the step count the payoff rule amortizes
    # the migration over; dry-run decides + records but never migrates.
    # Drift triggers additionally need --diagnostics (the monitor lives
    # there); capacity triggers work with --elastic alone.
    elastic: bool = False
    replan_cooldown_steps: int = 50
    replan_horizon_steps: int = 1000
    elastic_dry_run: bool = False
    # pipelined execution engine (engine/): fit runs chunks of N train
    # steps as ONE donated lax.scan dispatch over batches prefetched by a
    # background thread; checkpoints/preemption land at chunk boundaries.
    # 1 = the eager per-step loop (default; bit-identical trajectories
    # either way — docs/performance.md).
    pipeline_steps: int = 1
    # warm start (warmstart/): persistent plan + calibration + executable
    # caching under one directory — the second compile of the same job
    # skips the Unity search (plan cache hit replayed through the
    # import-strategy machinery), calibration only measures misses, and
    # JAX's persistent compilation cache serves the XLA executables.
    # Invalidation is conservative: any change to the graph, mesh,
    # search-relevant config, device kind, or calibration data misses.
    warmstart_dir: str = ""
    # serving engine (serving/): defaults for model.serve() — the fixed
    # continuous-batching slot count, the KV-cache length (0 → the model's
    # training sequence length), and the prefill chunk width (prompts are
    # processed through the decode graph in power-of-two length buckets up
    # to this, each bucket one cached executable).
    serve_slots: int = 4
    serve_max_seq_len: int = 0
    serve_prefill_chunk: int = 16
    # KV-cache layout: "paged" (block pool + per-slot page tables with
    # copy-on-write prefix sharing, serving/paged.py — the default) or
    # "contiguous" ((slots, max_seq+1, embed) per slot — the ablation/
    # fallback). Block size is pool rows per block; blocks=0 sizes the
    # pool from the per-chip HBM budget, capped at capacity parity.
    # The layout is part of the warm-start plan fingerprint.
    serve_kv_layout: str = "paged"
    serve_kv_block_size: int = 16
    serve_kv_blocks: int = 0
    # Cross-request radix prefix cache (serving/radix.py): cached prompt
    # blocks outlive their residents under LRU eviction, so a recurring
    # system prompt hits warm KV after a full drain. 0 restores
    # live-residents-only sharing (the bench ablation).
    serve_prefix_cache: int = 1
    # Disaggregated serving (serving/disagg.py): prefill and decode run
    # as two separately searched Unity plans on disjoint sub-meshes of
    # the same device set (Orca / vLLM lineage: compute-bound prefill vs
    # memory-bound decode want different layouts). serve_prefill_chips
    # sizes the prefill sub-mesh (0 → half the devices); serve_role marks
    # which side a decode-graph compile is for — it joins the warm-start
    # plan fingerprint so the two plans cache independently.
    serve_disaggregate: bool = False
    serve_prefill_chips: int = 0
    serve_role: str = ""  # "" | "prefill" | "decode" | "draft"
    # Speculative decoding (serving/speculative.py): serve_draft_chips
    # places the drafter LM on its own trailing sub-mesh (0 → colocated
    # with the target); serve_spec_k caps the per-round draft length the
    # acceptance-calibrated payoff gate may choose.
    serve_draft_chips: int = 0
    serve_spec_k: int = 4
    # First device this mesh draws from jax.devices() — sub-meshes over
    # disjoint device subsets (disaggregated serving) set it per side.
    mesh_device_offset: int = 0
    # static plan verification (analysis/): the ffcheck pass pipeline —
    # sharding dataflow, memory liveness, collective uniformity,
    # donation/aliasing — runs at compile on EVERY plan source; errors
    # abort compile with the findings in strategy_report.json's analysis
    # section. --no-verify-plan is the escape hatch (findings downgrade
    # to logged warnings).
    verify_plan: bool = True
    # ffrules substitution-rule verification (analysis/rules.py): every
    # rule loaded from --substitution-json is verified at load — symbolic
    # shape/dtype transfer, parallel-state soundness, the semantic-
    # equivalence oracle, and boundary-precondition fuzz — before it can
    # inject rewrites into the search; an unsound rule raises a
    # structured RuleVerificationError naming the rule and finding
    # class. --no-verify-rules downgrades refusals to logged warnings
    # (the verdict still lands in strategy_report.json's analysis
    # section).
    verify_rules: bool = True
    # ffsan runtime half (flexflow_tpu/sanitize.py): instrument the
    # train/eval/decode step with per-op finiteness probes (forward
    # values AND backward cotangents) so a NaN/inf is attributed to the
    # exact (op, fwd|bwd, step) that produced it — the nan_loss health
    # alert then names the culprit instead of just declaring the run
    # dead. Zero-cost when off (no probes are traced); value-identical
    # when on (probes are effectful identities).
    sanitize_numerics: bool = False
    # SPMD fingerprint barrier (analysis/spmd.py): before the first
    # step, every process cross-checks a digest of its step-executable
    # ingredients (plan fingerprint, strategy, donation registry +
    # whether the backend donates, update-spec layout, numerics policy)
    # against the coordinator's over broadcast_json; a mismatch raises
    # SPMDDivergenceError on every process in lockstep. One small
    # broadcast when on; nothing when off.
    spmd_barrier: bool = False
    # eager-loop diagnostics loss fetch cadence: the per-step device_get
    # is a full device drain; K>1 samples it every K-th step and the
    # health/drift rules then see one K-step-AVERAGED record per window
    # (raw per-window timings are bimodal under async dispatch — the
    # sampled step absorbs the drain the others skipped). Pipelined mode
    # gets every step's loss from the per-chunk vector regardless.
    health_sample_every: int = 1
    # ffscope (flexflow_tpu/scope/): op-grain profiling plane, flight
    # recorder, hang watchdog. --profile-every K captures every K-th
    # step under jax.profiler and attributes device time back to PCG
    # ops (the report's `profile` section); 0 = off (model.profile_step()
    # still arms a one-shot). The watchdog fires when no step boundary
    # lands within max(timeout, step-EMA x multiplier); 0 timeout = off.
    profile_every: int = 0
    watchdog_timeout: float = 0.0
    watchdog_multiplier: float = 10.0
    watchdog_abort: bool = False
    # flight-recorder ring capacity (always on; 0 disables)
    flight_events: int = 256

    def __post_init__(self):
        argv = sys.argv[1:]
        self.parse_args(argv)
        try:
            if (self.num_nodes == 1
                    and not getattr(self, "_nodes_explicit", False)
                    and jax.process_count() > 1):
                # zero-config multi-controller runs (MULTIHOST.md): one
                # process per host, so the fleet's node count is the
                # process count; an explicit --nodes (even --nodes 1)
                # always wins
                self.num_nodes = jax.process_count()
        except Exception:
            pass
        if self.workers_per_node == 0:
            try:
                if jax.process_count() > 1:
                    # multi-controller: local_device_count is already the
                    # per-host chip count
                    self.workers_per_node = max(1, jax.local_device_count())
                else:
                    # single process (incl. virtual multi-host meshes):
                    # divide the one process's devices across the nodes
                    self.workers_per_node = max(
                        1, jax.local_device_count() // max(1, self.num_nodes)
                    )
            except Exception:
                self.workers_per_node = 1

    @property
    def num_devices(self) -> int:
        return self.num_nodes * self.workers_per_node

    def mesh_shape(self) -> MeshShape:
        from .machine import MULTIHOST_AXES

        if self.mesh_axis_sizes is not None:
            sizes = tuple(self.mesh_axis_sizes)
            names = self.mesh_axis_names
            if (len(sizes) == len(MULTIHOST_AXES)
                    and names == DEFAULT_AXES):
                # --mesh dcn,data,model,pipe,seq (5 entries): explicit
                # multi-host mesh with a leading DCN axis
                names = MULTIHOST_AXES
            elif self.num_nodes > 1 and len(sizes) == len(names):
                # --nodes N with a single-slice mesh: prepend the DCN axis
                sizes = (self.num_nodes,) + sizes
                names = MULTIHOST_AXES
            return MeshShape(sizes, names)
        if self.num_nodes > 1:
            sizes = (self.num_nodes, self.workers_per_node) + (1,) * (
                len(MULTIHOST_AXES) - 2)
            return MeshShape(sizes, MULTIHOST_AXES)
        sizes = [self.num_devices] + [1] * (len(self.mesh_axis_names) - 1)
        return MeshShape(tuple(sizes), self.mesh_axis_names)

    # flag table mirrors model.cc:3556-3720
    def parse_args(self, argv: list[str]):
        i = 0
        while i < len(argv):
            a = argv[i]

            def val():
                nonlocal i
                i += 1
                return argv[i]

            if a in _PARITY_ONLY_FLAGS:
                # accepted so reference scripts run unmodified, but loudly:
                # these knobs configure simulator/runtime mechanics that
                # have no analog in the TPU recast (XLA owns workspace
                # sizing; the analytic cost model doesn't segment
                # transfers; the jitted step already overlaps update comm)
                print(f"flexflow_tpu: flag {a} accepted for reference CLI "
                      f"parity but has no effect in this framework",
                      file=sys.stderr)
            if a in ("-e", "--epochs"):
                self.epochs = int(val())
            elif a in ("-b", "--batch-size"):
                self.batch_size = int(val())
            elif a == "--lr":
                self.learning_rate = float(val())
            elif a == "--wd":
                self.weight_decay = float(val())
            elif a == "--printFreq":
                self.print_freq = int(val())
            elif a == "--budget" or a == "--search-budget":
                self.search_budget = int(val())
            elif a == "--alpha" or a == "--search-alpha":
                self.search_alpha = float(val())
            elif a == "--simulator-workspace-size":
                self.simulator_work_space_size = int(val())
            elif a == "--only-data-parallel":
                self.only_data_parallel = True
            elif a == "--enable-parameter-parallel":
                self.enable_parameter_parallel = True
            elif a == "--enable-attribute-parallel":
                self.enable_attribute_parallel = True
            elif a == "--enable-sample-parallel":
                self.enable_sample_parallel = True
            elif a == "--enable-inplace-optimizations":
                self.enable_inplace_optimizations = True
            elif a == "--search-overlap-backward-update":
                self.search_overlap_backward_update = True
            elif a == "--no-overlap-collectives":
                self.overlap_collectives = False
            elif a == "--weight-update-sharding" or a.startswith(
                    "--weight-update-sharding="):
                # value forms: --weight-update-sharding=stage3 (or a
                # separate token); bare flag = legacy force-on with the
                # stage decided by pricing (memory-bound -> 3, else 2)
                if "=" in a:
                    v = a.split("=", 1)[1]
                elif (i + 1 < len(argv)
                      and argv[i + 1] in ("stage2", "stage3", "off", "on",
                                          "2", "3")):
                    v = val()
                else:
                    v = "on"
                if v in ("stage3", "3"):
                    self.weight_update_sharding = True
                    self.weight_update_stage = 3
                elif v in ("stage2", "2"):
                    self.weight_update_sharding = True
                    self.weight_update_stage = 2
                elif v == "off":
                    self.weight_update_sharding = False
                    self.weight_update_stage = 0
                elif v == "on":
                    self.weight_update_sharding = True
                    self.weight_update_stage = None
                else:
                    raise ValueError(
                        f"--weight-update-sharding={v!r}: expected "
                        f"stage2|stage3|off|on")
            elif a == "--no-weight-update-sharding":
                self.weight_update_sharding = False
                self.weight_update_stage = 0
            elif a == "--fusion":
                self.perform_fusion = True
            elif a == "--profiling":
                self.profiling = True
            elif a == "--dataset":
                self.dataset_path = val()
            elif a == "--import-strategy" or a == "--import":
                self.import_strategy_file = val()
            elif a == "--export-strategy" or a == "--export":
                self.export_strategy_file = val()
            elif a == "--taskgraph":
                self.export_strategy_task_graph_file = val()
            elif a == "--compgraph":
                self.export_strategy_computation_graph_file = val()
            elif a == "--machine-model-version":
                self.machine_model_version = int(val())
            elif a == "--machine-model-file":
                self.machine_model_file = val()
            elif a == "--segment-size":
                self.simulator_segment_size = int(val())
            elif a == "--max-num-segments":
                self.simulator_max_num_segments = int(val())
            elif a == "--enable-propagation":
                self.enable_propagation = True
            elif a == "--memory-search":
                self.perform_memory_search = True
            elif a == "--search-num-nodes":
                self.search_num_nodes = int(val())
            elif a == "--search-num-workers":
                self.search_num_workers = int(val())
            elif a == "--base-optimize-threshold":
                self.base_optimize_threshold = int(val())
            elif a == "--calibrate":
                self.search_calibrate = int(val())
            elif a == "--search-mesh-shapes":
                self.search_mesh_shapes = True
            elif a == "--substitution-json":
                self.substitution_json_path = val()
            elif a == "--enable-substitutions":
                self.enable_substitutions = True
            elif a == "--nodes":
                self.num_nodes = int(val())
                self._nodes_explicit = True
            elif a == "-ll:gpu" or a == "-ll:tpu" or a == "--workers-per-node":
                self.workers_per_node = int(val())
            elif a == "-ll:cpu":
                self.cpus_per_node = int(val())
            elif a == "-ll:fsize":
                self.device_mem = float(val()) * 1024 * 1024
            elif a == "--mesh":
                # TPU-native: --mesh data,model,pipe,seq e.g. "8,4,1,1"
                self.mesh_axis_sizes = tuple(int(x) for x in val().split(","))
            elif a == "--seed":
                self.seed = int(val())
            elif a == "--checkpoint-dir":
                self.checkpoint_dir = val()
            elif a == "--checkpoint-every":
                self.checkpoint_every = int(val())
            elif a == "--checkpoint-every-seconds":
                self.checkpoint_every_seconds = float(val())
            elif a == "--checkpoint-keep":
                self.checkpoint_keep = int(val())
            elif a == "--auto-resume":
                self.auto_resume = True
            elif a == "--telemetry-dir":
                self.telemetry_dir = val()
            elif a == "--xprof-dir":
                self.xprof_dir = val()
            elif a == "--metrics-interval":
                self.metrics_interval = float(val())
            elif a == "--metrics-port":
                self.metrics_port = int(val())
            elif a == "--diagnostics":
                self.diagnostics = True
            elif a == "--drift-threshold":
                self.drift_threshold = float(val())
            elif a == "--elastic":
                self.elastic = True
            elif a == "--replan-cooldown-steps":
                self.replan_cooldown_steps = int(val())
            elif a == "--replan-horizon-steps":
                self.replan_horizon_steps = int(val())
            elif a == "--elastic-dry-run":
                self.elastic_dry_run = True
            elif a == "--health-abort-on":
                self.health_abort_on = tuple(
                    r.strip() for r in val().split(",") if r.strip())
            elif a == "--warmstart-dir":
                self.warmstart_dir = val()
            elif a == "--pipeline-steps":
                self.pipeline_steps = int(val())
            elif a == "--no-verify-plan":
                self.verify_plan = False
            elif a == "--no-verify-rules":
                self.verify_rules = False
            elif a == "--sanitize-numerics":
                self.sanitize_numerics = True
            elif a == "--spmd-barrier":
                self.spmd_barrier = True
            elif a == "--health-sample-every":
                self.health_sample_every = int(val())
            elif a == "--profile-every":
                self.profile_every = int(val())
            elif a == "--watchdog-timeout":
                self.watchdog_timeout = float(val())
            elif a == "--watchdog-multiplier":
                self.watchdog_multiplier = float(val())
            elif a == "--watchdog-abort":
                self.watchdog_abort = True
            elif a == "--flight-events":
                self.flight_events = int(val())
            elif a == "--serve-slots":
                self.serve_slots = int(val())
            elif a == "--serve-max-seq":
                self.serve_max_seq_len = int(val())
            elif a == "--serve-prefill-chunk":
                self.serve_prefill_chunk = int(val())
            elif a == "--serve-kv-layout":
                v = val()
                if v not in ("contiguous", "paged"):
                    raise ValueError(
                        f"--serve-kv-layout must be 'contiguous' or "
                        f"'paged', got {v!r}")
                self.serve_kv_layout = v
            elif a == "--serve-kv-block-size":
                self.serve_kv_block_size = int(val())
            elif a == "--serve-kv-blocks":
                self.serve_kv_blocks = int(val())
            elif a == "--serve-prefix-cache":
                self.serve_prefix_cache = int(val())
            elif a == "--serve-disaggregate":
                self.serve_disaggregate = True
            elif a == "--serve-prefill-chips":
                self.serve_prefill_chips = int(val())
            elif a == "--serve-draft-chips":
                self.serve_draft_chips = int(val())
            elif a == "--serve-spec-k":
                self.serve_spec_k = int(val())
            elif a == "--synthetic-input":
                self.synthetic_input = True
            elif a == "--allow-tensor-op-math-conversion":
                self.allow_tensor_op_math_conversion = True
            elif a == "--dtype":
                d = val().lower()
                table = {
                    "bf16": DataType.DT_BFLOAT16,
                    "bfloat16": DataType.DT_BFLOAT16,
                    "fp16": DataType.DT_HALF,
                    "half": DataType.DT_HALF,
                    "fp32": None,
                    "float32": None,
                }
                if d not in table:
                    raise ValueError(
                        f"--dtype {d!r}: expected one of {sorted(table)}")
                self.computation_dtype = table[d]
            # unknown flags are ignored, matching the reference's tolerant scan
            i += 1


class FFIterationConfig:
    """Per-iteration config (reference config.h:162-167): seq_length enables
    truncated-sequence batches."""

    def __init__(self):
        self.seq_length = -1

    def reset(self):
        self.seq_length = -1
