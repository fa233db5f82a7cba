"""ServingEngine: continuous-batching inference on the decode PCG.

The device side of serving (scheduler.py is the policy side; paged.py the
block-pool side): one donated jitted step (`Executor.build_decode_step`)
threads (params, kv-cache state, tokens, positions[, page tables]) and
returns the next token per slot, sampled in-program (greedy /
temperature-Gumbel per slot).

**Chunked prefill, interleaved with decode** (Orca's iteration-level
scheduling at sub-request grain): every `step()` runs exactly ONE device
call, carrying at most one prefill CHUNK (engine/chunking.plan_chunks
buckets, power-of-two widths) while every DECODING slot advances one
token in the same call — long prompts therefore never stall the
continuous batch, and a decoding slot's token stream is bit-identical
either way because rows are computed independently (dead elements point
at the scratch row/block).

**One step in flight.** A call of `step()` schedules, stages and
dispatches step n+1, THEN fetches step n's tokens, does their bookkeeping
and returns step n's completions: the host's work of an iteration runs
beside the device's step, not between two of them. Nothing the host does
for step n+1 needs the values of step n's tokens. Which slots decode, at
which positions, which chunk rides along and which blocks are written are
known when step n is dispatched (`_advance`: `length`, `prefill_pos`, the
prefill -> decode turn, `register_prompt`, and whether the token in
flight is the request's last by length); the token ids stay on the device
(`_build_token_feed`). A step crosses to the device three times: one put
of one packed array, one program (`feed`) that takes it apart in front of
the step, and the token vector's copy back, started at the launch
(`_stage_step`, `_packing`; docs/serving.md has the array's layout). What
needs the token values waits for the fetch (`_bookkeep`:
`generated`, EOS, the latency stamps, `decode_tokens`, `prefill_calls`,
completion, block release). An end by EOS is learnt one step late: the
row already dispatched for the request writes one cache row inside its
own reservation, and its token is dropped (`rows_discarded`). The step in
flight is completed at once where something needs its result now: the
step function handed back host tokens (a NumPy array), the sanitizer is
on, or `_complete_in_flight()` is called first by whatever reads or
replaces decode or scheduler state from outside the loop (`stats`,
`reset_stats`, `replan_mesh`, `profile_step`, `extract_kv`,
`admit_prefilled`, `_apply_copies`, a speculative round, the end of
`run_until_drained`).

**A chunk step's two batch layouts** (docs/serving.md). The RECTANGLE,
`(slots, q)` with q the chunk's bucket: the chunk in the admitted slot's
row, every decoding slot's token in column 0 of its own, the rest dead.
ROWS, `(slots + q, 1)`: the slots exactly as a pure-decode step has them,
then each token of the chunk as a single-query row of its own, at its own
position, carrying a copy of its slot's page-table row — the op writes
every row's K and V before any row reads, so row i of a chunk at `start`
attends `start + i + 1` keys by a length mask. The decode graph tells its
paged ops that rows past the slots are one chunk under one table row
(`chunk_from`), so the chunk's rows go through ONE multi-query call of
the paged chunk kernel, which walks that table row once for all of them
(kernels/flash_attention.py); where that kernel cannot tile the bucket
they go through the single-query paged decode kernel like the slots'
rows, each walking its slot's pages from the start (`kv_rows_walked`,
`stats()["chunk_kernel_steps"]`). The engine takes rows where a `(rows,
1)` call is served by the paged kernels
(ops/inc_attention.paged_rows_run_kernel, asked when the engine is built)
and the rectangle everywhere else: through the gather-and-einsum
reference a row costs a whole logical cache.

**KV layouts** (`--serve-kv-layout`, ServingSpec.kv_layout):
  - "paged" (default): per-layer block POOLS (num_blocks, block_size,
    embed) + per-slot page tables, with copy-on-write prompt-prefix
    sharing managed host-side by paged.BlockManager — N requests with one
    system prompt store (and prefill) it once. COW copies run through the
    donated `Executor.build_block_copy` executable before the step that
    writes.
  - "contiguous": the (slots, max_seq+1, embed) per-slot cache — the
    ablation/fallback layout.
Both are first-class stateful parallel tensors placed by the Unity
search; the two layouts are token-identical on the full test matrix.

Invariants the tests pin down (tests/test_serving.py):
  - greedy decode is token-identical to the teacher-forced training
    forward's argmax at every position;
  - an interleaved continuous batch is token-identical to serving each
    request alone (slot rows are computed independently);
  - paged decode is token-identical to contiguous decode, COW included;
  - the engine compile is a normal Unity compile: warm-start plan-cache
    hits apply (second serving compile of the same (model, slots,
    max_seq, mesh, kv layout) = 0 search evaluations), and contiguous and
    paged plans never share a cache address.

Telemetry (when the trained model has a session): `serve.compile` /
`serve.prefill` / `serve.step` spans, per-iteration queue-depth and
slot-occupancy counters, a `serve.request` event per completion carrying
time-to-first-token, and a `serve.summary` event with requests/s/chip and
decode tokens/s/chip. A step is one record in any trace: it gets an id
when it is scheduled and every span of it carries the id as `step`;
`serve.dispatch` also says what the step is and under which name a device
trace shows its executable (docs/observability.md, "Serving").
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Optional, Sequence

import numpy as np

from .. import telemetry
from ..engine.chunking import plan_chunks
from ..fftype import OperatorType as OT
from ..ops.base import BY_BLOCK, BY_POSITION, BY_SLOT
from .decode_graph import (
    FEEDS, HANDOFF, PREFIX, ServingSpec, adopt_params, build_decode_model,
    decode_states, refuse,
)
from .paged import SCRATCH_BLOCK, BlockManager
from .scheduler import ContinuousBatchingScheduler, Request


def _at_rest(decode_model, states: dict) -> dict:
    """What the decode model holds on the device between steps, for the
    `serve.compile` event: the bytes of its parameters and of its KV
    cache, and the bytes of one cache element as stored (2 under --dtype
    bf16, where both rest in the compute dtype; 4 under float32). A
    step's attention reads the cache as it is stored: the decode graph
    declares it in the dtype the queries have."""
    kv = [decode_model._state[name][leaf] for name, s in states.items()
          for leaf in s.names(BY_BLOCK, BY_POSITION)]
    return dict(
        weight_bytes_at_rest=sum(
            int(w.nbytes) for ws in decode_model._params.values()
            for w in ws.values()),
        kv_bytes_at_rest=sum(int(leaf.nbytes) for leaf in kv),
        kv_stored_itemsize=kv[0].dtype.itemsize if kv else 0)


@dataclasses.dataclass
class _Step:
    """One device step from its scheduling to the bookkeeping of its
    tokens. The engine keeps at most one dispatched and unfetched."""

    # what the call is staged from (host arrays)
    tokens: np.ndarray
    positions: np.ndarray
    read_idx: np.ndarray
    row_slots: Optional[np.ndarray]
    writes: dict          # {slot index: positions this step writes}
    # (slots,): the slot's token is the one the step before sampled for
    # it, still on the device / the row of this step's samples that is
    # the slot's next token, -1 for none
    from_sampled: np.ndarray
    sampled_row: np.ndarray
    # [(slot, request)] of the decoding slots: row slot.index samples the
    # request's next token
    decoding: list
    # (slot, request, tokens, laid out as rows, the row that samples the
    # request's first token or None before its last chunk)
    chunk: Optional[tuple]
    span: tuple           # (name, arguments) of the step's span
    # {"step": id}: what every span of the step carries, the id given out
    # when the step is scheduled
    tag: dict
    # serve.dispatch's arguments: the id, and what finds and sorts the
    # step on the device (`kind`, `rows`, a chunk's `bucket` and
    # `chunk_start`, `program`)
    launch: dict
    # the chunk's rows are one call of the paged chunk kernel
    chunk_kernel: bool = False
    spanned: bool = False
    # set at dispatch
    step_fn: object = None
    dispatched_t: float = 0.0
    sampled: object = None  # (rows,) tokens: the device's, or a host
    #                         step function's NumPy array
    at_once: bool = False   # nothing may be dispatched before its fetch
    # `sampled_row` on the device, where the step's rows are not the
    # slots (a chunk as rows): `_keep` files its samples by slot
    filed: object = None


@dataclasses.dataclass(frozen=True)
class _Packing:
    """How one step shape crosses to the device: where each host array
    of the step lies in the ONE int32 array that is put, and the program
    that takes it apart there (`ServingEngine._packing`)."""

    fields: dict     # {name: (its words, a slice; its shape)}
    size: int        # words
    feed: object     # the jitted program in front of the step


def _uncommitted(x):
    """A one-device program's output as an array committed to no device,
    the same buffer: what `jnp.asarray` of a host array gives. JAX
    commits every output of a program that read one committed array, and
    keys a jitted function's lowerings by which arguments are committed;
    `read_idx`, the key and the temperatures reach the step uncommitted
    wherever it is lowered ahead of its first call (the benchmark's jobs,
    `_stage_inputs`' callers), so that is how the step loop hands them
    over, or the step would be lowered and compiled a second time."""
    from jax._src.array import ArrayImpl

    return ArrayImpl(x.aval, x.sharding, x._arrays, committed=False,
                     _skip_checks=True)


class ServingEngine:
    def __init__(self, model, **overrides):
        import jax

        if jax.process_count() > 1:
            raise NotImplementedError(
                "serving runs single-controller for now (multi-host "
                "serving is the prefill/decode disaggregation item, "
                "ROADMAP)")
        cfg = model.config
        spec = ServingSpec(
            slots=cfg.serve_slots,
            max_seq_len=cfg.serve_max_seq_len,
            prefill_chunk=cfg.serve_prefill_chunk,
            kv_layout=cfg.serve_kv_layout,
            kv_block_size=cfg.serve_kv_block_size,
            kv_num_blocks=cfg.serve_kv_blocks,
        )
        for k, v in overrides.items():
            if not hasattr(spec, k):
                raise ValueError(f"serve(): unknown option {k!r}")
            setattr(spec, k, v)
        if spec.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if any(PREFIX in s.cannot for s in decode_states(model).values()):
            # a prefix found in the pool is useless without the recurrent
            # state at its end (snapshots at block boundaries are a
            # ROADMAP item): such a graph matches no prefix
            for option in ("prefix_cache", "prefix_sharing"):
                if overrides.get(option):
                    refuse(model, f"serve(): {option}=True", PREFIX,
                           error=ValueError)
            spec.prefix_cache = spec.prefix_sharing = False
        if spec.prefix_cache is None:
            spec.prefix_cache = bool(
                getattr(cfg, "serve_prefix_cache", 1))
        self.model = model
        self.spec = spec
        self.role = spec.role
        self.telemetry = model._telemetry
        with self._active():
            t0 = time.perf_counter()
            with telemetry.phase("serve.compile", slots=spec.slots):
                with telemetry.phase("serve.graph"):
                    self.decode_model, self.max_seq_len = (
                        build_decode_model(model, spec))
                with telemetry.phase("serve.adopt"):
                    self.adopted = adopt_params(self.decode_model, model)
                with telemetry.phase("serve.step_fn"):
                    self._step_fn = (
                        self.decode_model.executor.build_decode_step())
                # what the built graph's layers keep from token to token,
                # as their ops declare it: sizes, groups and refusals read
                # this
                states = self._states = decode_states(self.decode_model)
                at_rest = _at_rest(self.decode_model, states)
                with telemetry.phase("serve.pool"):
                    self._build_pool(spec, states, at_rest)
            telemetry.event(
                "serve.compile",
                duration_s=time.perf_counter() - t0,
                slots=spec.slots, max_seq_len=self.max_seq_len,
                prefill_chunk=spec.prefill_chunk,
                kv_layout=spec.kv_layout,
                plan_source=self.decode_model._plan_source,
                weights_adopted=self.adopted,
                **at_rest,
                mesh_axes={k: int(v) for k, v
                           in self.decode_model.mesh.shape.items()})
            if self.telemetry is not None:
                self.telemetry.flush()
        self.scheduler = ContinuousBatchingScheduler(
            spec.slots, self.max_seq_len)
        self.num_chips = int(self.decode_model.mesh.devices.size)
        self._rng = None  # lazily split jax PRNG for sampling steps
        # what a step's spans say of sparse latent attention
        # (docs/observability.md): the positions a row attends at the
        # most; and the expert layers, whose counts stats() reads
        self._sel_cap = next(
            (s.selected for s in states.values() if s.selected), 0)
        self._moe_nodes = [n.name
                           for n in self.decode_model.graph.topo_order()
                           if n.op_type == OT.OP_MOE_MLP]
        self._moe_base = (0, 0)
        # the recurrent layers' per-slot state (ops/delta_attention.py):
        # bytes all the layers keep for one slot, and the requests whose
        # first chunk was dispatched (each resets its slot's state)
        self._state_bytes_slot = sum(s.bytes_of(BY_SLOT)
                                     for s in states.values())
        self._state_resets = 0
        # graph input roles: exactly one token stream + the positions /
        # page-table feeds (+ constants, which the engine materializes)
        self._token_input = None
        self._const_inputs = {}
        for t in self.decode_model._input_tensors:
            if t.name in FEEDS:
                continue
            if hasattr(t, "constant_value"):
                self._const_inputs[t.name] = (
                    tuple(t.dims), t.dtype, t.constant_value)
            elif self._token_input is None:
                self._token_input = t.name
            else:
                raise ValueError(
                    f"serving needs exactly one token input; model has "
                    f"{self._token_input!r} and {t.name!r}")
        if self._token_input is None:
            raise ValueError("serving: model has no token input")
        # sanitizer baseline: events reported before this engine existed
        # (e.g. a training NaN earlier in the process) are not decode
        # corruption — only NEW reports surface as serve.nonfinite
        if self.decode_model.config.sanitize_numerics:
            from ..sanitize import get_monitor

            self._numerics_reported = {
                (e["op"], e["phase"]) for e in get_monitor().snapshot()}
        # disaggregation hooks (serving/disagg.py): the coordinator taps
        # completions BEFORE block release (to lift the prompt KV out of
        # the pool while the page table still maps it) and silences the
        # prefill side's request-grain completion accounting so the
        # merged metrics plane counts every request exactly once
        self._pre_release_hook = None
        self._suppress_completion_events = False
        self._iterations = 0  # step() calls that found work, ever
        # device steps dispatched, ever: step n's id is n, on every span
        # of the step (reset_stats leaves it, so an id names one step)
        self._step_ids = 0
        # the step dispatched and not fetched yet (module docstring), and
        # the requests a completion of it outside step() finished: the
        # next step() hands them to its caller
        self._in_flight: Optional[_Step] = None
        self._settled: list[Request] = []
        self._fetched_t = 0.0  # when the last fetch returned
        self._build_token_feed()
        # run accounting (stats())
        self._steps = 0  # device steps dispatched
        self._steps_ahead = 0  # of those, while the one before was unfetched
        # host-to-device puts and device programs issued to stage those
        # steps, before each one's own launch
        self._stage_puts = 0
        self._stage_programs = 0
        self._rows_discarded = 0  # rows whose request had ended by EOS
        self._decode_iterations = 0
        self._decode_tokens = 0
        self._prefill_tokens = 0
        self._prefill_calls = 0
        self._row_steps = 0  # of those, the ones laid out as rows
        # and of those, the ones whose chunk the paged chunk kernel took
        self._chunk_kernel_steps = 0
        # rows of the dispatched steps, and those that went through the
        # graph's row-wise tail (the final norm and the head)
        self._step_rows = 0
        self._head_rows = 0
        self._device_s = 0.0
        self._last_step_device_s = 0.0  # most recent device call's wall
        # ffpulse metrics plane: engine-owned registry so serving metrics
        # exist (and metrics_summary works) without a telemetry dir, and
        # reset_stats can zero the serving series alone. Every series the
        # step loop touches is created HERE — a serving step allocates no
        # metric objects (the overhead-guard invariant).
        from ..telemetry.metrics import MetricsRegistry

        reg = self.metrics = MetricsRegistry()
        self._h_queue_wait = reg.histogram("serve_queue_wait_s")
        self._h_ttft = reg.histogram("serve_ttft_s")
        self._h_tbt = reg.histogram("serve_tbt_s")
        self._h_e2e = reg.histogram("serve_e2e_s")
        self._h_step_device = reg.histogram("serve_step_device_s")
        self._g_slots_active = reg.gauge("serve_slots_active")
        self._g_slots_total = reg.gauge("serve_slots_total")
        self._g_slots_total.set(spec.slots)
        self._g_queue_depth = reg.gauge("serve_queue_depth")
        self._g_blocks_free = reg.gauge("serve_kv_blocks_free")
        self._g_blocks_used = reg.gauge("serve_kv_blocks_used")
        self._g_blocks_reserved = reg.gauge("serve_kv_blocks_reserved")
        self._c_cow_copies = reg.counter("serve_cow_copies_total")
        # radix prefix-cache plane (all pre-created — steady-state steps
        # allocate no metric objects): cached = blocks only the cache
        # holds (the evictable set), pinned = cache entries a live slot
        # also maps; hits/misses count admissions, evictions count nodes
        self._g_prefix_cached = reg.gauge(
            "serve_prefix_cache_blocks", state="cached")
        self._g_prefix_pinned = reg.gauge(
            "serve_prefix_cache_blocks", state="pinned")
        self._c_prefix_hits = reg.counter("serve_prefix_cache_hits_total")
        self._c_prefix_misses = reg.counter(
            "serve_prefix_cache_misses_total")
        self._c_prefix_evictions = reg.counter(
            "serve_prefix_cache_evictions_total")
        self._h_matched_prefix = reg.histogram("serve_matched_prefix_len")
        self._evictions_seen = 0
        self._c_tokens_out = reg.counter("serve_tokens_generated_total")
        self._c_prefill_tok = reg.counter("serve_prefill_tokens_total")
        self._c_completed = {
            r: reg.counter("serve_requests_completed_total", reason=r)
            for r in ("eos", "max_tokens", "length")}
        if self.telemetry is not None:
            self.telemetry.attach_registry(reg)
            if getattr(cfg, "metrics_interval", 0) or getattr(
                    cfg, "metrics_port", 0):
                self.telemetry.start_exporter(
                    interval_s=getattr(cfg, "metrics_interval", 0.0),
                    port=getattr(cfg, "metrics_port", 0))
        # elastic decode-mesh scaling (--elastic): poll the visible
        # device set between steps and grow/shrink the decode mesh via
        # replan_mesh; in-flight requests ride through untouched
        self._capacity_watcher = None
        self._steps_since_capacity_check = 0
        self.replan_decisions: list[dict] = []
        if getattr(cfg, "elastic", False):
            self.enable_autoscale()

    def _build_pool(self, spec, states, at_rest):
        """The cache's groups, the block manager over them and the
        pools' copy programs (the `serve.pool` phase)."""
        # paged layout: host-side block manager + the donated COW copy
        # executable; pool geometry comes from the BUILT op (resolve_
        # pool_blocks ran inside build_decode_model)
        self.block_manager = None
        self._copy_fn = None
        self._inject_fn = None  # lazily built KV-handoff landing pad
        # the cache's groups (serving/paged.py), {layer: its declaration}
        # each: the global one, and the pools of the layers that read the
        # window group's table; both empty in the contiguous layout
        # (a group is a fact of a leaf: a layer with leaves in both is in
        # both)
        self._groups = [{n: s for n, s in states.items()
                         if s.names(BY_BLOCK, group=g)} for g in (0, 1)]
        self._window_nodes = list(self._groups[1])
        # decoding slot-steps scheduled with the context inside the window
        self._under_window = 0
        # what a layer says it reads and writes in a step beyond what the
        # engine counts (DecodeState.step_counts; the layers that say so
        # are alike), and the measured window's totals of it
        self._step_counts = next(
            (s.step_counts for s in states.values() if s.step_counts), None)
        self._counted: dict = {}
        # bytes one block holds over the layers of (the global group, the
        # window group), as the pools are stored
        self._block_bytes = tuple(
            sum(s.bytes_of(BY_BLOCK, g) for s in group.values())
            for g, group in enumerate(self._groups))
        # [(layer, its keys' pool, its values')] of the layers the KV
        # handoff carries: the two leaves each declares by block
        self._handoff_leaves = sorted(
            (n, *s.names(BY_BLOCK)) for n, s in states.items()
            if s.names(BY_BLOCK) and HANDOFF not in s.cannot)
        if spec.kv_layout == "paged":
            windowed = list(self._groups[1].values())
            s = next(iter((self._groups[0] or self._groups[1]).values()))
            self.block_manager = BlockManager(
                s.blocks or s.window_blocks, s.block_size,
                -(-self.max_seq_len // s.block_size),
                sharing=spec.prefix_sharing,
                cross_time=bool(spec.prefix_cache),
                window_blocks=windowed[0].window_blocks if windowed else 0,
                window=max((w.window for w in windowed), default=0),
                window_span=spec.prefill_chunk,
                window_aligned=any(w.window_aligned for w in windowed))
            self._build_copy_fns()
        self._kv_itemsize = at_rest["kv_stored_itemsize"]
        self._chunk_rows = self._rows_serve_chunks()
        self._walk_block = self._rows_walk_block()
        self._chunk_tiles: dict[int, Optional[int]] = {}

    def _build_copy_fns(self):
        """The donated copy-on-write programs, one a cache group: block
        ids are a group's own (executor.build_block_copy)."""
        self._copy_fn, self._copy_fn_w = (
            self.decode_model.executor.build_block_copy(
                {n: s.names(BY_BLOCK, group=g) for n, s in group.items()})
            for g, group in enumerate(self._groups))

    def _rows_serve_chunks(self) -> bool:
        """Whether a step that carries a prefill chunk is laid out as
        single-query rows (module docstring): asked of the built decode
        graph's paged attention op, with the mesh its calls run on."""
        if self.block_manager is None:
            return False
        mesh = self.decode_model.executor.mesh
        return all(
            s.chunk_as_rows and s.chunk_as_rows(mesh, self._kv_itemsize)
            for group in self._groups for s in group.values())

    def _rows_walk_block(self) -> int:
        """Cache rows a page holds where the slots' rows read their whole
        context by the paged decode kernel's walk in every layer of the
        global group that keeps it (`DecodeState.rows_walk`, asked as
        `_rows_serve_chunks` asks), else 0: what a step's
        `kv_rows_copied` and `rows_handed` are counted by."""
        layers = list(self._groups[0].values())
        if not self._chunk_rows or not layers:
            return 0
        mesh = self.decode_model.executor.mesh
        walk = all(s.rows_walk and s.rows_walk(mesh, self._kv_itemsize)
                   for s in layers)
        return self.block_manager.block_size if walk else 0

    def _count(self, load: dict, **counts):
        """Counts of the step being scheduled: onto its span's `load`,
        and into the measured window's totals (`stats()`)."""
        for name, value in counts.items():
            self._counted[name] = self._counted.get(name, 0) + value
        load.update(counts)

    def _head_rows_of(self, rows: int, q: int) -> int:
        """Rows of a `(rows, q)` step that go through the graph's
        row-wise tail, the final norm and the head
        (Executor.build_decode_step): the slots' and a chunk's last one;
        every row where the graph has no such tail."""
        if not self.decode_model.executor.decode_tail()[0]:
            return rows * q
        return self.spec.slots + (rows > self.spec.slots)

    def _chunk_query_tile(self, b: int) -> Optional[int]:
        """Query rows a tile of the paged chunk kernel takes of a chunk
        that rides as `b` rows, None where the chunk's rows go through
        the single-query kernel (or the graph has another paged op):
        asked of the built graph's ops once a bucket, as the op asks
        itself when the bucket's program is traced
        (ops/inc_attention.paged_chunk_query_tile)."""
        if b not in self._chunk_tiles:
            mesh = self.decode_model.executor.mesh
            # one answer for the graph: its paged layers are alike
            tiles = {
                s.chunk_query_tile
                and s.chunk_query_tile(mesh, self._kv_itemsize, b) or None
                for group in self._groups for s in group.values()}
            self._chunk_tiles[b] = tiles.pop() if len(tiles) == 1 else None
        return self._chunk_tiles[b]

    def enable_autoscale(self, visible_devices_fn=None,
                         check_every: int = 16):
        """Arm between-steps capacity watching on the decode mesh: when
        the visible device set no longer matches it, the engine re-plans
        to the factorization CapacityWatcher proposes (grow or shrink).
        `visible_devices_fn` is injectable for tests."""
        from ..elastic import CapacityWatcher

        self._capacity_watcher = CapacityWatcher(
            self.decode_model, visible_devices_fn,
            check_every=max(1, int(check_every)))
        return self._capacity_watcher

    def _maybe_autoscale(self):
        """step() preamble: consume one capacity delta if the watcher
        sees one. Runs OUTSIDE the per-token device call — a re-plan
        happens between scheduler iterations, never inside one."""
        w = self._capacity_watcher
        if w is None:
            return
        self._steps_since_capacity_check += 1
        delta = w.check(self._steps_since_capacity_check)
        if delta is None or delta.new_axes is None:
            return
        self.replan_mesh(delta.new_axes, trigger="capacity")

    # ------------------------------------------------------------ session

    @contextlib.contextmanager
    def _active(self):
        """Route module-level telemetry to the trained model's session for
        the duration of one engine operation. No flush here — step() runs
        once per generated token, and a per-iteration flush would rewrite
        the whole trace buffer each time (quadratic I/O in the hot loop);
        the trace persists at compile end, drain end, and session close."""
        tel = self.telemetry
        if tel is None:
            yield
            return
        telemetry.activate(tel)
        try:
            yield
        finally:
            telemetry.deactivate(tel)

    # ------------------------------------------------------------ replan

    def replan_mesh(self, mesh_axis_sizes, trigger: str = "manual") -> dict:
        """Grow/shrink the decode mesh between scheduler iterations: a
        fresh decode compile at the new factorization (warm-start cache
        consulted, full verifier gate) followed by a verified, priced
        `migrate_state` of the live decode state — params AND the KV
        pools, whose global geometry is mesh-invariant (resolve_pool_
        blocks keys off the TRAINER's mesh), so every in-flight slot's
        cache rows move bit-exactly. The scheduler, block manager, page
        tables, and RNG are host-side and untouched — in-flight token
        streams continue exactly where they were. Returns the decision
        record (also in `self.replan_decisions` and the `replan`
        telemetry event stream)."""
        import copy as _copy

        from ..resilience.migrate import migrate_state

        axes = tuple(int(s) for s in mesh_axis_sizes)
        self._complete_in_flight()
        old_dec = self.decode_model
        with self._active():
            t0 = time.perf_counter()
            decision = {
                "trigger": str(trigger), "scope": "serving",
                "old_mesh_axes": {k: int(v)
                                  for k, v in old_dec.mesh.shape.items()},
                "new_axes": list(axes),
            }
            spec2 = _copy.copy(self.spec)
            spec2.config_overrides = dict(self.spec.config_overrides or {})
            spec2.config_overrides["mesh_axis_sizes"] = axes
            try:
                with telemetry.span("serve.replan", trigger=trigger):
                    new_dec, max_seq = build_decode_model(self.model, spec2)
                    decision["research_s"] = time.perf_counter() - t0
                    migrate_state(old_dec, new_dec)
            except Exception as e:
                decision["decision"] = "failed"
                decision["error"] = f"{type(e).__name__}: {e}"
                telemetry.event("replan", **decision)
                self.replan_decisions.append(decision)
                raise
            # swap the device surface; everything host-side (scheduler,
            # slots, block manager, stats) carries over untouched
            self.decode_model = new_dec
            self.max_seq_len = max_seq
            self._step_fn = new_dec.executor.build_decode_step()
            if self.block_manager is not None:
                self._build_copy_fns()
            self._inject_fn = None  # rebuilt lazily on the new executor
            self._build_token_feed()
            self._chunk_rows = self._rows_serve_chunks()
            self._walk_block = self._rows_walk_block()
            self._chunk_tiles = {}
            self.num_chips = int(new_dec.mesh.devices.size)
            trans = new_dec._transition or {}
            decision.update({
                "decision": "migrated",
                "new_mesh_axes": {k: int(v)
                                  for k, v in new_dec.mesh.shape.items()},
                "predicted_migration_s": trans.get("predicted_s"),
                "migration_measured_s": trans.get("measured_s"),
                "plan_origin": getattr(new_dec, "_plan_origin",
                                       new_dec._plan_source),
                "total_s": time.perf_counter() - t0,
            })
            telemetry.event("replan", **decision)
        self.replan_decisions.append(decision)
        return decision

    # ------------------------------------------------------------ intake

    def submit(self, prompt: Sequence[int], max_new_tokens: Optional[int] = None,
               temperature: Optional[float] = None,
               eos_id: Optional[int] = None) -> Request:
        """Queue one request (FCFS). Defaults come from the ServingSpec.
        A request the paged pool could NEVER serve (worst case exceeds
        the whole pool even capped at cache capacity) is rejected here,
        like the oversized-prompt check — not left to head-block the
        queue forever."""
        req = Request(
            prompt=[int(t) for t in prompt],
            max_new_tokens=(self.spec.max_new_tokens
                            if max_new_tokens is None else max_new_tokens),
            temperature=0.0 if temperature is None else float(temperature),
            eos_id=self.spec.eos_id if eos_id is None else eos_id,
        )
        mgr = self.block_manager
        if mgr is not None:
            needed = mgr.blocks_needed(len(req.prompt), req.max_new_tokens)
            if needed > mgr.num_blocks - 1:
                raise ValueError(
                    f"request needs {needed} KV blocks worst-case but the "
                    f"pool only has {mgr.num_blocks - 1} allocatable "
                    f"blocks; raise kv_num_blocks (or lower "
                    f"max_new_tokens / kv_block_size)")
        with self._active():
            telemetry.instant("serve.queued", trace=req.trace_id,
                              prompt_tokens=len(req.prompt))
        return self.scheduler.submit(req)

    # ------------------------------------------------------------ device step

    def _bucket(self, n: int) -> int:
        """Smallest power-of-two >= n, capped at prefill_chunk (which is
        itself the top bucket when it isn't a power of two) — the
        length-bucket set, so prompt raggedness costs O(log chunk)
        executables instead of one per distinct length."""
        b = 1
        while b < n:
            b *= 2
        return min(b, self.spec.prefill_chunk)

    def _stage_inputs(self, tokens: np.ndarray, positions: np.ndarray,
                      row_slots=None, tag=None) -> dict:
        """Stage one decode-graph call's input dict under the searched
        shardings: the token stream, positions, the page tables (paged
        layout), and the graph's constant feeds broadcast to the call's
        shape. Row i of the call reads and writes through slot
        `row_slots[i]`'s page table; by default row i is slot i (the
        rectangle, and a pure-decode step). Shared between the decode
        step and the speculative verify step (serving/speculative.py) so
        the two calls stage byte-identical feeds. Two spans, with the
        step's `tag` where a step stages, both named `serve.stage` as the
        span they lie in (a reader that asks what the host was in finds
        staging, whichever part): `part` "build" (the host's arrays, the
        page table from the block manager's lists) and "put" (the
        host-to-device puts)."""
        tag = tag or {}
        rows, q = tokens.shape
        dec = self.decode_model
        with telemetry.span("serve.stage", part="build", **tag):
            xs = {self._token_input: tokens, "positions": positions}
            if self.block_manager is not None:
                mgr = self.block_manager
                table = np.asarray(
                    [mgr.table(i) for i in range(self.spec.slots)], np.int32)
                xs["page_table"] = (table if row_slots is None
                                    else table[row_slots])
                if mgr.window is not None:
                    table = np.asarray(
                        [mgr.window_table(i)
                         for i in range(self.spec.slots)], np.int32)
                    xs["page_table_w"] = (table if row_slots is None
                                          else table[row_slots])
            if self._state_bytes_slot:
                xs["state_slot"] = np.asarray(
                    np.arange(rows) if row_slots is None else row_slots,
                    np.int32)[:, None]
            for name, (dims, dtype, value) in self._const_inputs.items():
                from ..fftype import dtype_to_jnp

                xs[name] = np.full((rows, q) + tuple(dims[2:]), value,
                                   dtype_to_jnp(dtype))
            specs = {}
            for name in xs:
                spec = dec._input_partition_spec(name)
                if spec is not None:
                    specs[name] = spec
        with telemetry.span("serve.stage", part="put", **tag):
            return dec.executor.shard_batch(xs, specs)

    def _build_token_feed(self):
        """What the step loop keeps on the device between steps, and the
        programs beside the step (docs/serving.md, "One step in
        flight"). `_sampled`: the tokens the step before sampled, by
        slot, which is that step's own vector wherever its rows are the
        slots (a step that only decodes, the rectangle); `_keep` files
        by slot the samples of a chunk step laid out as rows, whose
        vector is the step's own length, so that the program in front of
        the NEXT step has that step's shape and never a pair's.
        `_packings`: a step shape's packed array and its `feed` program
        (`_packing`), compiled when that shape's step first runs."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = self.decode_model.executor.mesh
        whole = NamedSharding(mesh, PartitionSpec())

        def keep(step_sampled, row):
            return step_sampled[jnp.maximum(row, 0)]

        self._keep = jax.jit(keep, out_shardings=whole)
        self._sampled = jax.device_put(
            np.zeros((self.spec.slots,), np.int32), whole)
        self._packings: dict[tuple, _Packing] = {}
        if self._rng is not None:
            # a new mesh: the key's chain goes on from the host's copy
            self._rng = jax.device_put(jax.random.wrap_key_data(
                np.asarray(jax.random.key_data(self._rng))), whole)

    def _packing(self, rows: int, q: int) -> _Packing:
        """The packed array's layout for a step of `rows` x `q` and the
        program that unpacks it, built once a shape from the feeds the
        decode graph declares (what `_stage_inputs` reads). The array,
        int32 words: the token stream and the positions (rows x q each),
        a row of each page table the graph reads a row, `state_slot`,
        `read_idx` and the temperatures' float32 bits (a word a row
        each), then a word a slot of `from_sampled` and, where the rows
        are not the slots (a chunk as rows), of `sampled_row`.

        The program, `feed(packed, sampled, rng)`: the feeds as the dict
        the step reads, each placed as `_stage_inputs` places it, the
        constant feeds made where they are read, the token column taken
        from `sampled` where `from_sampled` says; `read_idx`; the key's
        split, `rng, sub = split(rng)`, `sub` as its data; the
        temperatures; `rng`; `sampled_row` (or None)."""
        packing = self._packings.get((rows, q))
        if packing is not None:
            return packing
        import jax
        import jax.numpy as jnp
        from jax.sharding import (
            NamedSharding, PartitionSpec, SingleDeviceSharding)

        from ..fftype import dtype_to_jnp

        dec, slots = self.decode_model, self.spec.slots
        mesh = dec.executor.mesh
        whole = NamedSharding(mesh, PartitionSpec())
        shapes = {self._token_input: (rows, q), "positions": (rows, q)}
        if self.block_manager is not None:
            width = self.block_manager.table_width
            shapes["page_table"] = (rows, width)
            if self.block_manager.window is not None:
                shapes["page_table_w"] = (rows, width)
        if self._state_bytes_slot:
            shapes["state_slot"] = (rows, 1)
        feeds = tuple(shapes)
        shapes.update(read_idx=(rows,), temp=(rows,),
                      from_sampled=(slots,))
        if rows > slots:
            shapes["sampled_row"] = (slots,)
        fields, size = {}, 0
        for name, shape in shapes.items():
            words = int(np.prod(shape))
            fields[name] = (slice(size, size + words), shape)
            size += words
        consts = {
            name: ((rows, q) + tuple(dims[2:]), dtype_to_jnp(dtype), value)
            for name, (dims, dtype, value) in self._const_inputs.items()}
        token_input = self._token_input

        def feed(packed, sampled, rng):
            def take(name):
                words, shape = fields[name]
                return packed[words].reshape(shape)

            xs = {name: take(name) for name in feeds}
            tokens = xs[token_input]
            xs[token_input] = tokens.at[:slots, 0].set(jnp.where(
                take("from_sampled") != 0, sampled, tokens[:slots, 0]))
            for name, (shape, dtype, value) in consts.items():
                xs[name] = jnp.full(shape, value, dtype)
            rng, sub = jax.random.split(rng)
            temp = jax.lax.bitcast_convert_type(take("temp"), jnp.float32)
            return (xs, take("read_idx"), jax.random.key_data(sub), temp,
                    rng, take("sampled_row") if rows > slots else None)

        # the feeds as `_stage_inputs` places them; what the step takes
        # beside them lies on the mesh's one device (`_uncommitted`) or,
        # on a mesh of several, whole on each
        staged = {name: NamedSharding(
            mesh, dec._input_partition_spec(name) or PartitionSpec())
            for name in (*feeds, *consts)}
        beside = (SingleDeviceSharding(mesh.devices.flat[0])
                  if self.num_chips == 1 else whole)
        packing = self._packings[rows, q] = _Packing(
            fields, size, jax.jit(feed, out_shardings=(
                staged, beside, beside, beside, whole, whole)))
        return packing

    def _pack(self, step: _Step, packing: _Packing) -> np.ndarray:
        """The step's host arrays as the one array that is put."""
        slots = self.spec.slots
        rows = step.tokens.shape[0]
        row_slots = step.row_slots
        packed = np.empty((packing.size,), np.int32)

        def view(name):
            words, shape = packing.fields[name]
            return packed[words].reshape(shape)

        view(self._token_input)[:] = step.tokens
        view("positions")[:] = step.positions
        if self.block_manager is not None:
            mgr = self.block_manager
            tables = [("page_table", mgr.table)]
            if mgr.window is not None:
                tables.append(("page_table_w", mgr.window_table))
            for name, table_of in tables:
                table = view(name)
                table[:slots] = [table_of(i) for i in range(slots)]
                if row_slots is not None:
                    table[slots:] = table[row_slots[slots:]]
        if self._state_bytes_slot:
            view("state_slot")[:, 0] = (
                np.arange(rows) if row_slots is None else row_slots)
        view("read_idx")[:] = step.read_idx
        temp = np.zeros((slots,), np.float32)
        for s in self.scheduler.active_slots:
            temp[s.index] = s.request.temperature
        if row_slots is not None:
            temp = temp[row_slots]
        view("temp")[:] = temp.view(np.int32)
        view("from_sampled")[:] = step.from_sampled
        if row_slots is not None:
            view("sampled_row")[:] = step.sampled_row
        return packed

    def _stage_step(self, step: _Step) -> tuple:
        """A step's crossing to the device: ONE put, of everything the
        host knows about the step in one array, and ONE program, which
        takes it apart where the step reads it (`_packing`). -> `(xs,
        read_idx, sub, temp)`, the step's arguments, array for array what
        `_stage_inputs`, the select of the sampled tokens and the key's
        split gave one by one. The spans are `serve.stage`'s parts:
        `build` (the pack), `put`, `feed` (the program)."""
        import jax

        tag = step.tag
        with telemetry.span("serve.stage", part="build", **tag):
            packing = self._packing(*step.tokens.shape)
            packed = self._pack(step, packing)
        with telemetry.span("serve.stage", part="put", **tag):
            packed = jax.device_put(packed)
            self._stage_puts += 1
        with telemetry.span("serve.stage", part="feed", **tag):
            if self._rng is None:
                # the engine's first dispatch: a program built again
                # from here on is a recompile (telemetry/startup.py)
                telemetry.startup.steps_began()
                # placed as `feed` hands it back: one build a step shape
                self._rng = jax.device_put(
                    jax.random.key(self.decode_model.config.seed),
                    self._sampled.sharding)
            xs, read_idx, sub, temp, self._rng, step.filed = packing.feed(
                packed, self._sampled, self._rng)
            self._stage_programs += 1
            if self.num_chips == 1:
                read_idx, sub, temp = map(_uncommitted, (read_idx, sub, temp))
            sub = jax.random.wrap_key_data(sub)
        return xs, read_idx, sub, temp

    def _dispatch(self, step: _Step):
        """Stage one decode-graph call's inputs with their searched
        shardings and launch the donated step; its samples (a row samples
        at its slot's temperature) stay where the step function left
        them until `_fetch`, and their copy to the host starts here."""
        dec = self.decode_model
        # what the staging below issues before the step's own launch
        with telemetry.span("serve.stage", puts=1, programs=1, **step.tag):
            xs, read_idx, sub, temp = self._stage_step(step)
        step.step_fn = self._step_fn
        step.dispatched_t = time.perf_counter()
        with telemetry.span("serve.dispatch", **step.launch):
            dec._state, step.sampled = self._step_fn(
                dec._params, dec._state, xs, read_idx, sub, temp)
            # tokens already on the host (a host function in the step's
            # place reads the scheduler at its next call), or a step the
            # sanitizer has to drain: completed before anything else runs
            step.at_once = (isinstance(step.sampled, np.ndarray)
                            or bool(dec.config.sanitize_numerics))
            if not step.at_once:
                step.sampled.copy_to_host_async()
                self._sampled = (step.sampled if step.filed is None
                                 else self._keep(step.sampled, step.filed))

    def _fetch(self, step: _Step, ahead: bool) -> np.ndarray:
        """The sampled token of every row of a dispatched step, on the
        host; `ahead`: the step after it is already dispatched."""
        import jax

        with telemetry.span("serve.fetch", ahead=int(ahead), **step.tag):
            out = np.asarray(jax.device_get(step.sampled))
        # one step's time on the host's clock (not a device time): from
        # its dispatch, or from the fetch before it where it queued
        # behind that step, to its fetch. The serve_step_device_s
        # observation and the speculative decoder's cost feed; the spans
        # put the same interval on the profiler's clock
        now = time.perf_counter()
        dt = now - max(step.dispatched_t, self._fetched_t)
        self._fetched_t = now
        self._device_s += dt
        self._last_step_device_s = dt  # speculative decode-cost EMA feed
        self._h_step_device.observe(dt)
        if self.decode_model.config.sanitize_numerics:
            self._check_numerics()
        return out

    def _check_numerics(self):
        """Sanitizer check after a decode step (--sanitize-numerics):
        the token fetch above already drained the step, so the probe
        callbacks have fired; any new non-finite report is surfaced
        once per op as a serve.nonfinite event + error log instead of
        silently sampling from a NaN'd logits row."""
        import jax

        from ..sanitize import get_monitor
        from ..telemetry import log as fflog

        jax.effects_barrier()
        events = get_monitor().snapshot()
        seen = getattr(self, "_numerics_reported", set())
        for e in events:
            key = (e["op"], e["phase"])
            if key in seen:
                continue
            seen.add(key)
            telemetry.event("serve.nonfinite", op=e["op"],
                            phase=e["phase"])
            fflog.error(
                "serving: non-finite tensor at op %s (%s) during "
                "decode — the KV cache or weights are numerically "
                "dead", e["op"], e["phase"])
        self._numerics_reported = seen

    # ------------------------------------------------------------ paged

    def _can_admit(self, req: Request) -> bool:
        """Paged admission gate: reserve the request's worst case
        (prompt + max_new_tokens in blocks) so a decode write can never
        exhaust the pool mid-flight. A True answer IS the reservation —
        the scheduler admits exactly when the gate passes."""
        return self.block_manager.reserve(
            req.request_id, len(req.prompt), req.max_new_tokens,
            prompt=req.prompt)

    def _apply_copies(self, copies):
        """COW copies on the pool state from outside a step (a handoff's
        admission, a warm-up): the step in flight is completed first."""
        self._complete_in_flight()
        self._copy_blocks(copies)

    def _copy_blocks(self, copies):
        """Run this iteration's COW copies on the pool state, one donated
        dispatch a cache group that has any, padded to a power-of-two width
        with scratch→scratch no-op pairs (one cached executable per
        bucket)."""
        if not copies:
            return
        import jax.numpy as jnp

        dec = self.decode_model
        self._c_cow_copies.inc(len(copies))
        for group, fn in ((0, self._copy_fn), (1, self._copy_fn_w)):
            mine = [c for c in copies if c.group == group]
            if not mine:
                continue
            b = 1
            while b < len(mine):
                b *= 2
            src = np.full((b,), SCRATCH_BLOCK, np.int32)
            dst = np.full((b,), SCRATCH_BLOCK, np.int32)
            for i, c in enumerate(mine):
                src[i], dst[i] = c.src, c.dst
            with telemetry.span("serve.cow_copy", blocks=len(mine),
                                group=group):
                dec._state = fn(dec._state, jnp.asarray(src),
                                jnp.asarray(dst))

    def _prepare_writes(self, slot_positions: dict[int, range], tag=None):
        """Paged pre-step bookkeeping: make every block this iteration
        writes slot-owned (allocating / COW-copying via the BlockManager)
        and apply the copies to the device pools BEFORE the step runs;
        `tag` is the step's id, as its spans carry it."""
        if self.block_manager is None:
            return
        with telemetry.span("serve.prepare_writes", **(tag or {})):
            copies = []
            for idx, positions in slot_positions.items():
                copies.extend(
                    self.block_manager.ensure_writable(idx, positions))
            self._copy_blocks(copies)

    def _note_completion(self, slot, req: Request):
        hook = self._pre_release_hook
        if hook is not None:
            hook(slot, req)
        if self.block_manager is not None:
            self.block_manager.release(slot.index)
        if self._suppress_completion_events:
            # disagg prefill side: the request is not DONE, it is handed
            # off — the decode side (or the coordinator, for requests
            # that truly finish at prefill) records the completion once
            return
        self.record_completion(req)

    def record_completion(self, req: Request):
        """Request-grain completion accounting: latency histogram,
        reason counter, and the `serve.request` event the doctor's
        drained-TTFT identity counts. Split out of `_note_completion` so
        the disaggregated coordinator can record a request that finished
        at prefill (EOS on the first token) on the decode side, which
        owns completion accounting for the pair."""
        if req.e2e_s is not None:
            self._h_e2e.observe(req.e2e_s)
        c = self._c_completed.get(req.finish_reason)
        if c is None:  # unknown reason: labeled child created off-path
            c = self.metrics.counter("serve_requests_completed_total",
                                     reason=req.finish_reason or "unknown")
        c.inc()
        telemetry.instant("serve.done", request=req.request_id,
                          trace=req.trace_id, reason=req.finish_reason)
        telemetry.event(
            "serve.request", request_id=req.request_id,
            trace=req.trace_id,
            prompt_tokens=len(req.prompt), new_tokens=len(req.generated),
            finish_reason=req.finish_reason,
            ttft_s=req.ttft_s,
            queue_wait_s=req.queue_wait_s,
            matched_prefix_len=req.matched_prefix_len,
            total_s=(req.finish_t - req.submit_t
                     if req.finish_t is not None else None))

    # ------------------------------------------------------------ disagg

    def kv_pool_layers(self) -> list[str]:
        """Pool-bearing state node names in SORTED order — the layer
        axis of extract_kv / inject rows. Both handoff sides sort, so
        layer i's extracted rows land in layer i's pool."""
        return [name for name, *_ in self._handoff_leaves]

    def extract_kv(self, slot_index: int, num_tokens: int):
        """Lift a slot's prompt-extent KV blocks off this engine's
        pools: (layers, blocks, block_size, embed) K and V row stacks.
        The disaggregated coordinator calls this from its pre-release
        hook — the completing slot's page table still maps the blocks."""
        import jax

        refuse(self.decode_model,
               "serving/engine.py: extract_kv (the KV handoff)", HANDOFF)
        self._complete_in_flight()
        mgr = self.block_manager
        nblk = -(-num_tokens // mgr.block_size)
        idx = np.asarray(mgr.table(slot_index)[:nblk], np.int32)
        st = self.decode_model._state
        ks = [st[name][k][idx] for name, k, _ in self._handoff_leaves]
        vs = [st[name][v][idx] for name, _, v in self._handoff_leaves]
        ks, vs = jax.device_get((ks, vs))
        return (np.stack([np.asarray(k) for k in ks]),
                np.stack([np.asarray(v) for v in vs]))

    def admit_prefilled(self, req: Request, first_token: int,
                        rows_k, rows_v) -> Optional[int]:
        """Decode-side admission of a request whose prompt KV was
        computed on the prefill pool: reserve the worst case, take a
        free slot with every prompt row accounted for, map any
        radix-cached prefix (the cross-pool hit path — a cached extent
        costs NO injection), COW/allocate the uncovered extent, inject
        the handed-off rows, and publish the prompt into this side's
        cache. Returns the number of blocks injected (0 = full prefix
        hit), or None when no slot or reservation is available — the
        coordinator retries next iteration, FCFS order preserved."""
        sched = self.scheduler
        mgr = self.block_manager
        if mgr is None:
            raise ValueError(
                "disaggregated admission requires the paged KV layout")
        refuse(self.decode_model, "serving/engine.py: admit_prefilled "
               "(the KV handoff)", HANDOFF)
        self._complete_in_flight()
        if not sched.free_slots:
            return None
        if not mgr.reserve(req.request_id, len(req.prompt),
                           req.max_new_tokens):
            return None
        slot = sched.admit_prefilled(req, first_token)
        L = len(req.prompt)
        injected = 0
        with self._active():
            telemetry.instant("serve.admitted", trace=req.trace_id,
                              slot=slot.index, prefilled=True,
                              queue_wait_s=req.queue_wait_s)
            mgr.bind_reservation(req.request_id, slot.index)
            matched = mgr.match_prefix(req.prompt)
            skip = mgr.admit(slot.index, req.prompt)
            req.matched_prefix_len = matched
            self._h_matched_prefix.observe(matched)
            (self._c_prefix_hits if skip else self._c_prefix_misses).inc()
            if skip:
                telemetry.instant(
                    "serve.prefix_hit", slot=slot.index,
                    shared_tokens=skip, matched_prefix_len=matched,
                    prompt_tokens=L)
            bs = mgr.block_size
            nlb = -(-L // bs)
            if matched < L:
                # the partially-matched tail block (if any) COWs here,
                # so the injection below never writes a cached block
                self._apply_copies(
                    mgr.ensure_writable(slot.index, range(matched, L)))
                lb0 = matched // bs
                blocks = mgr.table(slot.index)[lb0:nlb]
                self._inject_rows(blocks, rows_k[:, lb0:nlb],
                                  rows_v[:, lb0:nlb])
                injected = nlb - lb0
            mgr.register_prompt(slot.index, req.prompt)
        return injected

    def _inject_rows(self, blocks, rows_k, rows_v):
        """One donated inject dispatch, padded to a power-of-two block
        count with (scratch, zero-rows) pairs — one cached executable
        per bucket, like the COW copies."""
        import jax.numpy as jnp

        if self._inject_fn is None:
            self._inject_fn = (
                self.decode_model.executor.build_kv_inject(
                    self._handoff_leaves))
        b = 1
        while b < len(blocks):
            b *= 2
        idx = np.full((b,), SCRATCH_BLOCK, np.int32)
        idx[:len(blocks)] = blocks
        layers = rows_k.shape[0]
        pk = np.zeros((layers, b) + rows_k.shape[2:], rows_k.dtype)
        pv = np.zeros((layers, b) + rows_v.shape[2:], rows_v.dtype)
        pk[:, :len(blocks)] = rows_k
        pv[:, :len(blocks)] = rows_v
        dec = self.decode_model
        with telemetry.span("serve.kv_inject", blocks=len(blocks)):
            dec._state = self._inject_fn(
                dec._state, jnp.asarray(idx), jnp.asarray(pk),
                jnp.asarray(pv))

    # ------------------------------------------------------------ iterate

    def _publish_slot_gauges(self, prefilling, decoding):
        """Per-iteration occupancy/pool gauges — shared between the
        plain step and the speculative verify round (speculative.py), so
        both iteration shapes feed the same metrics plane."""
        sched = self.scheduler
        self._g_slots_active.set(len(prefilling) + len(decoding))
        self._g_queue_depth.set(sched.queue_depth)
        if self.block_manager is not None:
            mgr = self.block_manager
            self._g_blocks_free.set(mgr.free_blocks)
            self._g_blocks_used.set(mgr.blocks_in_use)
            self._g_blocks_reserved.set(mgr.reserved_total)
            cached_only = mgr.cached_only_blocks
            self._g_prefix_cached.set(cached_only)
            self._g_prefix_pinned.set(mgr.cached_blocks - cached_only)
            ev = mgr.stats.radix_evictions
            if ev > self._evictions_seen:
                self._c_prefix_evictions.inc(ev - self._evictions_seen)
                self._evictions_seen = ev
        telemetry.counter("serve.slots", {
            "active": len(prefilling) + len(decoding),
            "queue": sched.queue_depth,
            "occupancy": (len(prefilling) + len(decoding))
            / max(1, len(sched.slots))})

    def step(self) -> list[Request]:
        """ONE scheduler iteration (the Orca unit), ONE device call: admit
        pending requests into free slots, pick AT MOST ONE prefill chunk
        (the longest-waiting prefilling slot's next plan_chunks bucket),
        and advance every decoding slot one token in the same call — the
        chunked-prefill interleave that keeps long prompts from stalling
        the continuous batch. The call dispatches that step and THEN
        fetches the tokens of the step the call before it dispatched
        (module docstring): it returns the requests that one completed."""
        sched = self.scheduler
        self._maybe_autoscale()
        done_before = len(sched.completed)
        with self._active():
            if sched.drained and self._in_flight is None:
                self._publish_slot_gauges([], [])
            else:
                self._iterations += 1
                with telemetry.span("serve.iteration",
                                    iteration=self._iterations):
                    self._iterate()
        return self._take_settled() + sched.completed[done_before:]

    def _take_settled(self) -> list[Request]:
        done, self._settled = self._settled, []
        return done

    def _complete_in_flight(self):
        """Fetch the step in flight, if there is one, and do its
        bookkeeping: what reads or replaces decode state, or the
        scheduler's, from outside the step loop calls this first."""
        step, self._in_flight = self._in_flight, None
        if step is None:
            return
        sched = self.scheduler
        done_before = len(sched.completed)
        with self._active():
            self._complete(step)
        self._settled += sched.completed[done_before:]

    def _complete(self, step: _Step):
        with self._span_of(step):
            tokens = self._fetch(step, ahead=False)
        self._bookkeep(step, tokens)

    def _span_of(self, step: _Step):
        """The step's `serve.step` / `serve.prefill` span, once: opened
        by the call that dispatches it where nothing was in flight, else
        by the call that fetches it."""
        if step.spanned:
            return contextlib.nullcontext()
        step.spanned = True
        name, args = step.span
        return telemetry.span(name, **args)

    def _iterate(self):
        """step()'s work, in the phases the profiler's trace shows
        (docs/observability.md): the span of the step this call
        completes (serve.prefill or serve.step) over serve.schedule,
        serve.prepare_writes, serve.stage, serve.dispatch and
        serve.advance of the NEXT step and serve.fetch of its own; then
        serve.bookkeep. Every span says in `step` whose it is."""
        prev, self._in_flight = self._in_flight, None
        if prev is not None and prev.step_fn is not self._step_fn:
            # the step function was replaced since: its replacement finds
            # the scheduler as the fetched tokens leave it
            self._complete(prev)
            prev = None
        fetched = []
        with contextlib.ExitStack() as span:
            if prev is not None:
                span.enter_context(self._span_of(prev))
            step = self._schedule()
            if step is not None:
                self._prepare_writes(step.writes, step.tag)
                if prev is None:
                    span.enter_context(self._span_of(step))
                self._step_ids += 1
                self._steps += 1
                self._steps_ahead += prev is not None
                self._step_rows += step.tokens.size
                self._head_rows += step.span[1]["head_rows"]
                self._dispatch(step)
                with telemetry.span("serve.advance", **step.tag):
                    self._advance(step)
            if prev is not None:
                fetched.append((prev, self._fetch(prev, step is not None)))
            if step is not None and step.at_once:
                fetched.append((step, self._fetch(step, ahead=False)))
        for of, tokens in fetched:
            self._bookkeep(of, tokens)
        if step is not None and not step.at_once:
            self._in_flight = step

    def _schedule(self) -> Optional[_Step]:
        """Admissions, the chunk choice and the next step's arrays, from
        the scheduler as the dispatch of the step before left it; None
        where no slot has a row to run."""
        sched = self.scheduler
        # the id of the step this makes, if it makes one: an annotation
        # takes its arguments when it is entered
        tag = {"step": self._step_ids + 1}
        with telemetry.span("serve.schedule", **tag):
            gate = (self._can_admit
                    if self.block_manager is not None else None)
            admitted = sched.admissions(can_admit=gate)
            for slot, req in admitted:
                if self.block_manager is not None:
                    self.block_manager.bind_reservation(
                        req.request_id, slot.index)
                self._h_queue_wait.observe(req.queue_wait_s)
                telemetry.instant("serve.admitted", trace=req.trace_id,
                                  slot=slot.index,
                                  queue_wait_s=req.queue_wait_s)
            prefilling = [s for s in sched.slots if s.prefilling]
            decoding = [s for s in sched.slots if s.decoding]
            self._publish_slot_gauges(prefilling, decoding)
            if not prefilling and not decoding:
                return None

            # ---- choose this iteration's single prefill chunk (FCFS)
            pre = min(prefilling, key=lambda s: s.admit_seq) \
                if prefilling else None
            n = b = 0
            if pre is not None:
                mgr = self.block_manager
                if mgr is not None and pre.index not in mgr._tables:
                    # LAZY page-table build: matched against the registry
                    # at first-chunk time, so a burst of same-prefix
                    # requests still shares — the first resident's last
                    # chunk was dispatched, and its blocks registered, by
                    # the time the next one prefills (one chunk per
                    # iteration, FCFS)
                    matched = mgr.match_prefix(pre.request.prompt)
                    skip = mgr.admit(pre.index, pre.request.prompt)
                    pre.prefill_pos = skip
                    pre.request.matched_prefix_len = matched
                    self._h_matched_prefix.observe(matched)
                    (self._c_prefix_hits if skip
                     else self._c_prefix_misses).inc()
                    if skip:
                        telemetry.instant(
                            "serve.prefix_hit", slot=pre.index,
                            shared_tokens=skip,
                            matched_prefix_len=matched,
                            prompt_tokens=len(pre.request.prompt))
                L = len(pre.request.prompt)
                start, n = plan_chunks(
                    pre.prefill_pos, L, self.spec.prefill_chunk)[0]
                b = self._bucket(n)
            # a chunk step's layout (module docstring): the rectangle
            # (slots, q), or the slots' rows then one row a chunk token
            slots = self.spec.slots
            by_rows = pre is not None and self._chunk_rows
            rows, q = (slots + b, 1) if by_rows else (slots, max(b, 1))
            tokens = np.zeros((rows, q), np.int32)
            # scratch positions everywhere but live elements: no other
            # slot's cache state moves (row max_seq for the contiguous
            # layout; the paged op routes clipped positions to the
            # reserved scratch block)
            positions = np.full((rows, q), self.max_seq_len, np.int32)
            read_idx = np.zeros((rows,), np.int32)
            row_slots = None
            writes: dict[int, range] = {}
            from_sampled = np.zeros((slots,), bool)
            sampled_row = np.full((slots,), -1, np.int32)
            chunk = None
            # context rows this step's attention must read, and those
            # its kernels do read where the chunk rides as rows: a tile
            # of the chunk kernel reads the context once for its rows, up
            # to the last of them; through the single-query kernel chunk
            # row i walks its slot's start + i + 1 rows, the chunk's
            # earlier ones again
            kv_rows = kv_rows_walked = sum(s.length + 1 for s in decoding)
            tile = self._chunk_query_tile(b) if by_rows else None
            if pre is not None:
                piece = pre.request.prompt[start:start + n]
                at = np.arange(start, start + n, dtype=np.int32)
                if by_rows:
                    tokens[slots:slots + n, 0] = piece
                    positions[slots:slots + n, 0] = at
                    row_slots = np.r_[np.arange(slots),
                                      np.full((b,), pre.index)]
                    first_row = slots + n - 1
                else:
                    tokens[pre.index, :n] = piece
                    positions[pre.index, :n] = at
                    read_idx[pre.index] = n - 1
                    first_row = pre.index
                # the final chunk's last live logits row samples the
                # request's first token
                if start + n < L:
                    first_row = None
                else:
                    sampled_row[pre.index] = first_row
                chunk = (pre, pre.request, n, bool(by_rows), first_row)
                writes[pre.index] = range(start, start + n)
                kv_rows += start + n
                kv_rows_walked += (
                    sum(start + min(n, t + tile) for t in range(0, n, tile))
                    if tile else n * start + n * (n + 1) // 2)
            for s in decoding:
                # the token the step in flight samples for the slot is
                # not on the host yet: `feed` takes it from the device
                if s.ahead:
                    from_sampled[s.index] = True
                else:
                    tokens[s.index, 0] = s.last_token
                positions[s.index, 0] = s.length
                sampled_row[s.index] = s.index
                writes[s.index] = range(s.length, s.length + 1)

            # the counts of the step's span, which opens when the step is
            # dispatched or when it is fetched (`_span_of`)
            load = dict(kv_rows=int(kv_rows),
                        kv_itemsize=self._kv_itemsize,
                        admitted=len(admitted), pending=sched.queue_depth,
                        head_rows=self._head_rows_of(rows, q))
            if by_rows:
                load.update(rows=rows, kv_rows_walked=int(kv_rows_walked))
            if self._walk_block:
                # the single-query kernel's call: the slots' rows, and a
                # chunk's where the chunk kernel does not take them (a
                # dead row stands at the scratch position)
                from ..kernels.flash_attention import paged_walk_counts

                at = positions[:rows if by_rows and not tile else slots, 0]
                copied, handed = paged_walk_counts(
                    np.where(at < self.max_seq_len, at + 1, 0),
                    self._walk_block)
                self._count(load, kv_rows_copied=copied, rows_handed=handed)
            if self._window_nodes:
                # rows a window layer's attention reads: a row's window,
                # or its context where that is shorter
                w = self.block_manager.window
                window = w.window
                # decoding slots whose context is still inside the
                # window: a window layer reads all of it, and the slot
                # has given no block back yet
                under = sum(s.length + 1 <= window for s in decoding)
                self._under_window += under
                load.update(
                    window_rows=int(
                        sum(s.length + 1 - w.first_row(s.length)
                            for s in decoding)
                        + (sum(t + 1 - w.first_row(t)
                               for t in range(start, start + n))
                           if pre is not None else 0)),
                    under_window=under,
                    # the group's running count as the step is scheduled:
                    # blocks the steps before it gave back
                    window_blocks_freed=(
                        self.block_manager.stats.window_blocks_freed))
            if self._step_counts:
                self._count(
                    load, **self._step_counts([s.length for s in decoding]))
            if self._sel_cap:
                # a layer's indexer scores every cached row of every live
                # row's context; its attention reads the selected ones.
                # `index_rows`: the indexer keys the step reads from the
                # pool, a slot's context each and a chunk's once for all
                # of its rows
                ctx = [s.length + 1 for s in decoding]
                index_rows = sum(ctx)
                if pre is not None:
                    ctx += range(start + 1, start + n + 1)
                    index_rows += start + n
                load.update(ctx_rows=int(sum(ctx)),
                            sel_rows=int(sum(min(c, self._sel_cap)
                                             for c in ctx)),
                            index_rows=int(index_rows))
            if self._state_bytes_slot:
                # slots whose recurrent state the step updates (the
                # decoding ones and the chunk's), and the bytes of it:
                # read once and written once where the kernel serves
                updated = len(decoding) + (pre is not None)
                load.update(state_rows=updated,
                            state_bytes=updated * self._state_bytes_slot)
            load.update(tag)
            span = ("serve.prefill", dict(
                slot=pre.index, trace=pre.request.trace_id,
                start=start, tokens=n,
                prompt_tokens=len(pre.request.prompt),
                decoding=len(decoding), **load)) if pre is not None else \
                ("serve.step", dict(active=len(decoding), **load))
            # a device trace names an execution by its jitted function
            program = "jit_" + getattr(self._step_fn, "__name__",
                                       type(self._step_fn).__name__)
            launch = dict(tag, kind="decode", rows=rows, program=program)
            if pre is not None:
                launch.update(kind="chunk", bucket=b, chunk_start=start)
            return _Step(
                tag=tag, launch=launch,
                tokens=tokens, positions=positions, read_idx=read_idx,
                row_slots=row_slots, writes=writes,
                from_sampled=from_sampled, sampled_row=sampled_row,
                decoding=[(s, s.request) for s in decoding], chunk=chunk,
                chunk_kernel=tile is not None, span=span)

    def _advance(self, step: _Step):
        """The bookkeeping of a dispatched step that needs no token
        value: the scheduler's state now includes the step, and the next
        one is scheduled from it."""
        sched = self.scheduler
        if step.chunk is not None:
            pre, req, n, _, first_row = step.chunk
            self._state_resets += bool(self._state_bytes_slot
                                       and pre.prefill_pos == 0)
            pre.prefill_pos += n
            if first_row is not None:  # the prompt's last chunk
                pre.length = len(req.prompt)
                pre.prefill_pos = None
                if self.block_manager is not None:
                    self.block_manager.register_prompt(
                        pre.index, req.prompt)
                sched.note_dispatch(pre)
        for s, _ in step.decoding:
            s.length += 1
            sched.note_dispatch(s)

    def _bookkeep(self, step: _Step, tokens: np.ndarray):
        """The bookkeeping of a fetched step: what needs its tokens, and
        the run's counts of completed work."""
        with telemetry.span("serve.bookkeep", **step.tag):
            if step.chunk is not None:
                pre, req, n, by_rows, first_row = step.chunk
                self._prefill_tokens += n
                self._c_prefill_tok.inc(n)
                self._prefill_calls += 1
                self._row_steps += by_rows
                self._chunk_kernel_steps += step.chunk_kernel
                if first_row is not None:  # TTFT lands here
                    self._note_token(pre, req, tokens[first_row])
            if step.decoding:
                self._decode_iterations += 1
            for s, req in step.decoding:
                self._note_token(s, req, tokens[s.index])

    def _note_token(self, slot, req: Request, token):
        if slot.request is not req:
            # the request ended by EOS in the step before this one, which
            # was dispatched by then: the row wrote one cache row inside
            # the request's own reservation, and its token is dropped
            self._rows_discarded += 1
            return
        self._decode_tokens += 1
        prev_t = req.last_token_t
        if self.scheduler.note_token(slot, int(token)):
            self._note_completion(slot, req)
        self._observe_token(req, prev_t)

    def _observe_token(self, req: Request, prev_t):
        """Latency bookkeeping for one sampled token: the request's first
        token lands TTFT, every later one lands a TBT observation."""
        self._c_tokens_out.inc()
        if prev_t is None:
            self._h_ttft.observe(req.ttft_s)
            telemetry.instant("serve.first_token", trace=req.trace_id,
                              ttft_s=req.ttft_s)
        else:
            self._h_tbt.observe(req.last_token_t - prev_t)

    @contextlib.contextmanager
    def _maybe_xprof(self):
        """--xprof-dir beyond fit: the serving step loop runs under the
        same `jax.profiler.trace` passthrough the training loop gets
        (model.py wraps fit), so decode/prefill show up in XProf and in
        ffscope attribution. No-op without the flag; a trace already
        active (e.g. a surrounding capture) wins without erroring."""
        xdir = getattr(getattr(self.model, "config", None),
                       "xprof_dir", None)
        if not xdir:
            yield
            return
        import jax

        try:
            jax.profiler.start_trace(xdir)
        except Exception:
            yield
            return
        try:
            yield
        finally:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass

    def profile_step(self) -> Optional[dict]:
        """Capture ONE scheduler iteration under `jax.profiler` and
        attribute its device time to the serving model's ops (ffscope) —
        the serving twin of `model.profile_step()`. Returns the profile
        section (also kept as `self.last_profile`), or None when the
        capture could not start (e.g. a trace is already active)."""
        import jax

        from ..scope.profile import StepProfiler

        self._complete_in_flight()
        prof = StepProfiler()
        it = self._decode_iterations
        if not prof.begin(it):
            return None
        try:
            # the whole of the step inside the capture; what it
            # completes goes to the caller of the next step()
            self._settled += self.step()
            self._complete_in_flight()
            jax.effects_barrier()
        except BaseException:
            prof.abandon()
            raise
        names = [n.name for n in self.model.graph.topo_order()] \
            if getattr(self.model, "graph", None) is not None else []
        section = prof.end(it, names)
        prof.close()
        if section is not None:
            section["source"] = "serving"
            with self._active():
                for row in section["ops"]:
                    if row["measured_s"] > 0:
                        telemetry.observe("op_time_s", row["measured_s"],
                                          op=row["name"])
        self.last_profile = section
        return section

    def run_until_drained(self, max_iterations: int = 0) -> list[Request]:
        """Iterate until queue and slots are empty; returns every request
        completed during the call. `max_iterations` > 0 bounds the loop
        (a safety valve for drivers)."""
        done: list[Request] = []
        t0 = time.perf_counter()
        it = 0
        with self._maybe_xprof():
            while not self.scheduler.drained:
                done.extend(self.step())
                it += 1
                if max_iterations and it >= max_iterations:
                    break
            # the last step dispatched (one row that outran an EOS, or
            # the step the iteration bound stopped behind)
            self._complete_in_flight()
            done.extend(self._take_settled())
        self.note_drain(time.perf_counter() - t0)
        return done

    def note_drain(self, wall_s: float):
        """Close one measured window: record its wall-clock, emit the
        summary event, and export a drained metrics snapshot. The
        drain loop above calls this; open-loop drivers (serve_bench's
        --arrival-rate mode) step the engine themselves and call it
        directly when their trace completes."""
        self._last_wall_s = wall_s
        with self._active():
            telemetry.event("serve.summary", **self.metrics_summary())
        if self.telemetry is not None:
            self.telemetry.write_metrics_snapshot(
                reason="serve_drain", drained=bool(self.scheduler.drained))
            self.telemetry.flush()

    def generate(self, prompts: Sequence[Sequence[int]],
                 **request_kw) -> list[list[int]]:
        """Convenience batch API: submit every prompt, drain, return the
        generated token lists in submission order."""
        reqs = [self.submit(p, **request_kw) for p in prompts]
        self.run_until_drained()
        return [r.generated for r in reqs]

    # ------------------------------------------------------------ stats

    def reset_stats(self) -> None:
        """Zero the run accounting (and the completed-request list) —
        benchmark drivers call this after a warm-up drain so the measured
        window starts clean. Live slots/queue state is untouched."""
        self._complete_in_flight()
        self.scheduler.completed.clear()
        self._steps = 0
        self._steps_ahead = 0
        self._stage_puts = 0
        self._stage_programs = 0
        self._rows_discarded = 0
        self._decode_iterations = 0
        self._decode_tokens = 0
        self._prefill_tokens = 0
        self._prefill_calls = 0
        self._row_steps = 0
        self._chunk_kernel_steps = 0
        self._step_rows = 0
        self._head_rows = 0
        self._under_window = 0
        self._counted = {}
        self._state_resets = 0
        self._device_s = 0.0
        self._last_wall_s = 0.0
        self._moe_base = self._moe_totals()
        # zero the serving series (objects survive — the step loop holds
        # references); the stats_reset event marks the window boundary so
        # doctor's TTFT identity counts serve.request events after it
        self.metrics.reset(prefix="serve_")
        self._g_slots_total.set(self.spec.slots)
        with self._active():
            telemetry.event("serve.stats_reset")
        if self.block_manager is not None:
            from .paged import PagedStats

            fresh = PagedStats()
            # live blocks carry over — the measured window's peak must
            # still dominate what is resident when it opens
            fresh.blocks_in_use_peak = self.block_manager.blocks_in_use
            if self.block_manager.window is not None:
                fresh.window_blocks_in_use_peak = (
                    self.block_manager.window.blocks_in_use)
            self.block_manager.stats = fresh
            # the eviction-delta poll restarts from the fresh counter
            self._evictions_seen = 0

    def _moe_totals(self) -> tuple:
        """(assignments computed, assignments dropped) by the expert
        layers that hold a share of their experts, since the engine was
        built: running counts the op keeps in its state on the device
        (ops/moe.py), fetched here and nowhere in the step loop."""
        import jax

        st = self.decode_model._state
        leaves = [(st[n]["assignments_total"], st[n]["dropped_total"])
                  for n in self._moe_nodes
                  if "assignments_total" in st.get(n, {})]
        if not leaves:
            return (0, 0)
        got = np.asarray(jax.device_get(leaves)).astype(np.int64)
        return (int(got[:, 0].sum()), int(got[:, 1].sum()))

    def stats(self) -> dict:
        """Aggregate run metrics; rates are per chip of the decode mesh
        over the last drain's WALL-clock window — scheduler and telemetry
        overhead included, since that is the throughput a client sees
        (`device_s` reports the device-busy slice separately;
        requests/s/chip is the ROADMAP's serving bench target). Counts
        are of fetched steps: the step in flight is completed first."""
        self._complete_in_flight()
        completed = self.scheduler.completed
        sched = self.scheduler
        wall = getattr(self, "_last_wall_s", 0.0) or 0.0
        ttfts = [r.ttft_s for r in completed if r.ttft_s is not None]
        # drain-time accounting gap: requests that never emitted a token
        # (still queued, mid-prefill at shutdown, or defensively a
        # completed request with no first_token_t) are EXCLUDED from the
        # TTFT population above by design — a queue-depth artifact is not
        # a latency sample — but must not vanish: they count here.
        no_token = (len(sched.pending)
                    + sum(1 for s in sched.active_slots
                          if s.request.first_token_t is None)
                    + sum(1 for r in completed
                          if r.first_token_t is None))
        out = {
            "no_token_requests": no_token,
            "slots": self.spec.slots,
            "max_seq_len": self.max_seq_len,
            "num_chips": self.num_chips,
            "requests_completed": len(completed),
            # device steps dispatched; of those, the ones dispatched
            # while the step before was unfetched; rows dispatched for a
            # request that had ended by EOS, their tokens dropped
            "iterations": self._steps,
            "steps_ahead": self._steps_ahead,
            # what staging those steps crossed to the device with, before
            # each one's own launch: one put and one program a step
            "stage_puts": self._stage_puts,
            "stage_programs": self._stage_programs,
            "rows_discarded": self._rows_discarded,
            "decode_iterations": self._decode_iterations,
            "decode_tokens": self._decode_tokens,
            "prefill_tokens": self._prefill_tokens,
            "prefill_calls": self._prefill_calls,
            # of those, the steps laid out as single-query rows: all of
            # them where the paged kernel serves the rows, else none
            "row_steps": self._row_steps,
            "chunk_kernel_steps": self._chunk_kernel_steps,
            # the rows those steps carried, and the rows that went on
            # through the final norm, the head and the sampler
            "step_rows": self._step_rows,
            "head_rows": self._head_rows,
            "wall_s": wall,
            "device_s": self._device_s,
            "plan_source": self.decode_model._plan_source,
            "kv_layout": self.spec.kv_layout,
        }
        out["kv_hbm_bytes_per_layer"] = self.kv_bytes_per_layer()
        if self._state_bytes_slot:
            # recurrent layers: slots that hold state, its bytes on the
            # device (all slots, all layers), slots reset for a new request
            out["state_slots"] = self.spec.slots
            out["state_bytes"] = self._state_bytes_slot * self.spec.slots
            out["state_resets"] = self._state_resets
        if self._moe_nodes:
            done, dropped = self._moe_totals()
            out["moe_assignments"] = done - self._moe_base[0]
            out["moe_dropped"] = dropped - self._moe_base[1]
        if self.block_manager is not None:
            mgr = self.block_manager
            out.update({
                "prompt_tokens": mgr.stats.prompt_tokens,
                "prefix_hit_tokens": mgr.stats.shared_tokens,
                "evictions": mgr.stats.radix_evictions,
                "kv_block_size": mgr.block_size,
                "kv_pool_blocks": mgr.num_blocks,
                "kv_blocks_in_use_peak": mgr.stats.blocks_in_use_peak,
                "prefix_hit_rate": mgr.stats.prefix_hit_rate,
                "prefix_shared_tokens": mgr.stats.shared_tokens,
                "cow_copies": mgr.stats.cow_copies,
                # radix prefix-cache plane: cross-time hits are the
                # prefixes that survived their residents (the cache's
                # whole reason to exist); evictions price the budget
                "prefix_cache": bool(self.spec.prefix_cache),
                "cross_time_hits": mgr.stats.cross_time_hits,
                "radix_evictions": mgr.stats.radix_evictions,
                "radix_evicted_blocks": mgr.stats.radix_evicted_blocks,
                "prefix_cached_blocks": mgr.cached_blocks,
                "prefix_cached_only_blocks": mgr.cached_only_blocks,
                # slots-at-fixed-HBM headline: how many contiguous
                # max_seq slots the pool's PEAK working set would buy —
                # the vLLM capacity-recovery metric
                "kv_peak_vs_contiguous": (
                    self.spec.slots * (self.max_seq_len + 1)
                    / max(1, mgr.stats.blocks_in_use_peak
                          * mgr.block_size)),
            })
            # the cache by group (serving/paged.py): blocks a live slot
            # or the prefix cache holds, their bytes over the group's
            # layers, and the tokens whose global rows are held (whole
            # blocks): `kv_pool_bytes` / `kv_cached_tokens` is what a
            # held token costs, every layer counted
            held = len(mgr._refcount)
            w = mgr.window
            out.update({
                "kv_blocks_in_use": mgr.blocks_in_use,
                "kv_blocks_held": held,
                "kv_pool_bytes": (
                    held * self._block_bytes[0]
                    + (w.blocks_held * self._block_bytes[1] if w else 0)),
                "kv_cached_tokens": held * mgr.block_size,
            })
            if w is not None:
                out.update({
                    "kv_window_pool_blocks": w.num_blocks,
                    "kv_window_blocks_in_use": w.blocks_in_use,
                    "kv_window_blocks_in_use_peak":
                        mgr.stats.window_blocks_in_use_peak,
                    "kv_window_blocks_held": w.blocks_held,
                    "window_blocks_freed": mgr.stats.window_blocks_freed,
                    "under_window": self._under_window,
                    "window_cow_copies": mgr.stats.window_cow_copies,
                    "window_pins_dropped": mgr.stats.window_pins_dropped,
                    "kv_window_pool_bytes":
                        w.blocks_held * self._block_bytes[1],
                })
        out.update(self._counted)
        if ttfts:
            out["ttft_p50_s"] = float(np.percentile(np.asarray(ttfts), 50))
            out["ttft_max_s"] = float(max(ttfts))
        if wall > 0:
            out["requests_per_sec_per_chip"] = (
                len(completed) / wall / self.num_chips)
            out["decode_tokens_per_sec_per_chip"] = (
                self._decode_tokens / wall / self.num_chips)
        return out

    def metrics_summary(self) -> dict:
        """stats() plus request-grain latency percentiles rebuilt from
        the engine's mergeable histograms — callable MID-RUN (histograms
        are cumulative; no drained completed-list needed), and the
        drain-time serve.summary event is exactly this dict. Old stats()
        keys are preserved; `ttft_p50_s`/`ttft_max_s` are re-derived from
        the histogram (estimate within one bucket width, max exact)."""
        from ..telemetry.metrics import percentile_from_hist

        out = self.stats()
        for short, h in (("queue_wait", self._h_queue_wait),
                         ("ttft", self._h_ttft),
                         ("tbt", self._h_tbt),
                         ("e2e", self._h_e2e)):
            if h.count == 0:
                continue
            hd = h.to_dict()
            for q in (50, 95, 99):
                out[f"{short}_p{q}_s"] = percentile_from_hist(hd, q)
            out[f"{short}_max_s"] = h.max
            out[f"{short}_mean_s"] = h.sum / h.count
        return out

    def kv_bytes_per_layer(self) -> int:
        """Resident KV bytes ONE attention layer holds under this
        engine's layout (unsharded, in the dtype the cache rests in): the
        pool for paged — counted once, however many page tables map its
        blocks — or the full (slots, max_seq+1) region for contiguous.
        The serving bench's slots-at-fixed-HBM comparison reads this."""
        for name, s in self._states.items():
            kv = s.names(BY_BLOCK, BY_POSITION)
            if kv:
                return sum(int(self.decode_model._state[name][leaf].nbytes)
                           for leaf in kv)
        return 0
