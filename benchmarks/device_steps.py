"""A serving step as one record, from the engine's spans to the device's
own interval for it, in a traced run.

The engine gives a step an id when it schedules it and every span of the
step carries it as `step` (docs/observability.md, "Serving");
`ff/serve.dispatch` also says what the step is (`kind` decode | chunk,
`rows`, a chunk's `bucket` and `chunk_start`) and under which name the
device trace shows its executable (`program`). This module finds each
step's execution on the device and hands both sides to the readers of
`device_step_ms.*`, `chunk_share_pct.serve`, `host_iter_ms.serve`,
`host_stage_ms.serve` and `step_join_pct.serve`.

What a trace of this installation holds (one `--trace 1` run of
`c13b-serve-chat` on a v5e, listed line by line in PR 35; JAX 0.9.0,
libtpu 0.0.34). Planes: `/device:TPU:0`, `/host:CPU`, and five without a
line that matters here (`#Chip0 Host Interface`, `#Chip0 Misc`,
`/host:metadata`, `/device:CUSTOM:Megascale Trace`, `Task Environment`).
`/device:TPU:0` has five lines:
  - `XLA Modules`: **one event an execution of an executable**, named
    `jit_decode_step(<fingerprint>)`, `jit_feed(..)`, `jit_keep(..)`,
    `jit__threefry_split(..)`, `jit__unstack(..)`, `jit_copy_blocks(..)`;
    start and duration are the execution's first to last operation; stats
    `run_id` (the process's launch count), `replica_id`, `queue_id`,
    `core_type`, `device_offset_ps`, `device_duration_ps`, `_ct` / `_c`.
    A fingerprint is one compiled program: the step of each chunk bucket
    has its own. An iteration shows `jit_keep`, (`jit_copy_blocks`,)
    `jit_feed`, `jit__threefry_split`, `jit__unstack`, `jit_decode_step`.
  - `XLA Ops` (one event an instruction; `benchmarks/trace.py`),
    `Async XLA Ops` (copies and slices from start to done, stats `hlo_op`,
    `flow`, `id`), `Scalar Unit` and `TC Overlay` (both empty).
`/host:CPU` has a line a thread:
  - `python`: the `bench/` and `ff/` annotations with their arguments as
    stats, and JAX's own `PjitFunction(decode_step)`, `ParseArguments`,
    `DevicePut`, `shard_args`, `PJRT_LoadedExecutable_Execute linkage`
    (`_pt` / `_p`: a flow id), `PythonRefManager::CollectGarbage`;
  - `main/<tid>`: the runtime under those calls
    (`PJRT_LoadedExecutable_Execute`, `tpu::System::Execute` with a flow
    id, `MemoryAllocation`, `Wait for donation holds`, ...);
  - `pjrt-tpu-tasks/<tid>` (three) and `tfrt-non-blocking-queue/<tid>`:
    the transfers (`Linearize`, `H2D Dispatch`, `D2H Dispatch`) and
    `DoEnqueueProgram`, which **carries the device event's `run_id`**,
    but on a worker thread, after the launch, and for 2,911 of the 3,607
    executions of that run only;
  - `futex-default-SDomainT/<tid>` (`CompleteCallbacks` with `run_id`,
    `tpu::System::Execute=>Done`, `MemoryDeallocation`) and
    `EventFDAsyncWorker/<tid>` (the transfers' completions).
No event on the launching thread inside `ff/serve.dispatch` carries
`run_id`, and the chain of flow ids that leads to one is broken for a
fifth of the executions, so the join is by order, checked: **the k-th
execution of a step program is the k-th dispatched step**, counted from
the trace's first dispatch and from the execution that makes the most
pairings sound (a trace that begins with a step in flight holds an
execution or two of steps whose dispatch it does not hold: `LEADING`).
The check is causality: an execution starts after its
`serve.dispatch` opens and ends before its `serve.fetch` closes (steps
are serial on one chip). The two planes' clocks do not agree to that
grain: in that run every execution *starts* 25 to 180 us *before* its
dispatch span opens as the trace has them, and the launch is a further
0.3-0.9 ms into the span. So the check asks for ONE offset between the
clocks under which the pairings are causal (`clock_offset`): each pairing
allows the offsets from `dispatch opens - execution starts` to `fetch
closes - execution ends`, a pairing is sound where the offsets that most
pairings allow are among its own, and a join that slipped by a step
leaves no common offset once steps differ in length. Besides, a
fingerprint has to run one shape of step only (`rows`, `bucket`): a slip
pairs a chunk's program with a decode step's span. The middle of the
common offsets is what a device time is moved by when the host's span
over it is looked up (the gap table); the table prints both ends. A
program whose spans carry no `step` (every commit before PR 35), or a
trace without the `XLA Modules` line, leaves nothing to read: `record` is
None and so is every reader.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Optional

from benchmarks import harness, program_spans, trace

MODULES_LINE = "XLA Modules"
PROGRAM = re.compile(r"^(.*)\((\d+)\)$")
DISPATCH, FETCH = "ff/serve.dispatch", "ff/serve.fetch"
STEP_SPANS = ("ff/serve.step", "ff/serve.prefill")
ITERATION, STAGE = "ff/serve.iteration", "ff/serve.stage"
STAGE_PARTS = ("build", "put", "feed")   # `part` of the spans inside it
GAPS_SHOWN = 10
LEADING = 3     # executions tried as the first dispatch's: the engine
#                 keeps one step in flight


@dataclasses.dataclass
class Step:
    """One joined step: the arguments of its dispatch span, and its
    execution on chip 0 (nanoseconds on the trace's clock)."""

    id: int
    kind: str
    bucket: int         # 0 for a step that only decodes
    chunk_start: int
    rows: int
    start: float
    end: float
    busy_ns: float      # of [start, end), an operation ran
    idle_before_ns: float   # chip 0 idle since the step before it ended
    args: dict          # of its serve.step / serve.prefill and dispatch

    @property
    def ms(self) -> float:
        return (self.end - self.start) / 1e6


@dataclasses.dataclass
class Iteration:
    """One `ff/serve.iteration` span and the host nanoseconds inside it."""

    kind: Optional[str]         # of the step it dispatched, None for none
    ns: float
    fetch_ns: float             # in its serve.fetch spans
    stage_ns: Optional[float]   # in its serve.stage span, None for none
    parts: dict                 # {one of STAGE_PARTS: ns}


@dataclasses.dataclass
class Gap:
    """An interval inside the window in which no operation ran."""

    ns: float
    at_ns: float                # its start, from the window's
    then: Optional[Step]        # the first joined step that starts after
    host: str                   # the innermost `ff/` span the host was in
    host_step: Optional[int]    # and that span's `step`


@dataclasses.dataclass
class Record:
    steps: list          # Step, by start: those joined soundly
    dispatched: int      # steps dispatched and fetched inside the window
    busy_ns: dict        # {"steps", "cut", "between"}: see `read`
    window_busy_ns: float
    clock_ns: tuple      # (least, most) the host's clock is ahead of chip 0's
    gaps: list           # Gap, the GAPS_SHOWN longest first
    iterations: list     # Iteration, those wholly inside the window


def innermost(spans, t):
    """The shortest of `spans` over time t, or None."""
    over = [s for s in spans if s[1] <= t < s[2]]
    return min(over, key=lambda s: s[2] - s[1]) if over else None


def clock_offset(allowed) -> tuple:
    """(least, most) of the values that most of the [least, most]
    intervals `allowed` hold: where the most of them overlap."""
    ends = sorted([(a, 0) for a, _ in allowed] + [(b, 1) for _, b in allowed])
    best, held, common = 0, 0, (0.0, 0.0)
    for k, (t, closes) in enumerate(ends):
        held += -1 if closes else 1
        if not closes and held > best:
            best, common = held, (t, ends[k + 1][0])
    return common


def busy_inside(busy, intervals) -> list:
    """For each [start, end) of `intervals`, the nanoseconds of it that
    the merged, sorted `busy` intervals cover."""
    starts = [a for a, _ in busy]
    ends = [b for _, b in busy]
    upto = [0.0]
    for a, b in busy:
        upto.append(upto[-1] + (b - a))
    out = []
    for lo, hi in intervals:
        i, j = bisect.bisect_right(ends, lo), bisect.bisect_left(starts, hi)
        if j <= i:
            out.append(0.0)
            continue
        out.append(upto[j] - upto[i] - max(0.0, lo - starts[i])
                   - max(0.0, ends[j - 1] - hi))
    return out


def events(profile, tr):
    """(executions, spans, windows) as the profile has them, uncut:
    chip 0's modules line as (start, end, name) by start, or None; the
    host plane's `ff/` events as (name, start, end, stats) by start; the
    `bench/window` spans."""
    modules, spans, windows = None, [], []
    for plane in profile.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == tr.chips[0].index:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules = sorted(
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events)
        elif plane.name == trace.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    a, b = e.start_ns, e.start_ns + e.duration_ns
                    if e.name == trace.WINDOW_SPAN:
                        windows.append((a, b))
                    elif e.name.startswith(program_spans.PREFIX):
                        spans.append((e.name, a, b, dict(e.stats)))
    spans.sort(key=lambda s: (s[1], -s[2]))
    return modules, spans, windows


def join(whole, runs, first: int, lo):
    """(sound pairings, the clocks' offsets they share) of the steps
    `whole`, (place among the dispatches, dispatch opens, fetch closes,
    arguments), with the executions `runs` counted from `first`. A
    pairing: (offsets it allows, arguments, start, end, when the
    execution before it ended)."""
    shape_of, paired = {}, []
    for i, opened, fetched, args in whole:
        if first + i >= len(runs):
            continue                # dispatched, and never seen to run
        start, end, name = runs[first + i]
        shape = (args.get("rows"), args.get("bucket", 0))
        # the offsets of the host's clock from the device's under which
        # this pairing is causal
        allowed = (opened - start, fetched - end)
        if (allowed[0] <= allowed[1]
                and shape_of.setdefault(name, shape) == shape):
            paired.append((allowed, args, start, end, max(
                lo, runs[first + i - 1][1] if first + i else lo)))
    clock = clock_offset([p[0] for p in paired])
    return [p for p in paired
            if p[0][0] <= clock[0] and clock[1] <= p[0][1]], clock


def iterations_in(spans, lo, hi) -> list:
    """An Iteration for each `ff/serve.iteration` span inside [lo, hi]."""
    found, opens = [], [s[1] for s in spans]
    for name, a, b, _ in spans:
        if name != ITERATION or a < lo or b > hi:
            continue
        within = [s for s in spans[bisect.bisect_left(opens, a):
                                   bisect.bisect_right(opens, b)]
                  if s[2] <= b]
        kinds = [s[3].get("kind") for s in within if s[0] == DISPATCH]
        stage = [s[2] - s[1] for s in within
                 if s[0] == STAGE and "part" not in s[3]]
        found.append(Iteration(
            kinds[0] if kinds else None, b - a,
            sum(s[2] - s[1] for s in within if s[0] == FETCH),
            stage[0] if stage else None,
            {part: sum(s[2] - s[1] for s in within
                       if s[0] == STAGE and s[3].get("part") == part)
             for part in STAGE_PARTS}))
    return found


def read(profile, tr):
    """The Record of a profile whose device lines `tr` (a trace.Trace)
    holds, or None where there is nothing to read. Every time is taken
    as the profile has it: a span cut by the window is left out, not
    clipped.

    `busy_ns` shares out chip 0's busy time inside the window among the
    executions of the modules line: `steps` inside the joined steps'
    intervals, `cut` inside executions of a step program that were not
    joined (the window's two ends cut their step's spans), `between`
    inside every other program's (`jit_feed`, `jit_keep`, the rng split,
    block copies). Their sum is the window's busy time where no
    operation runs outside an execution."""
    lo, hi = tr.window
    modules, spans, windows = events(profile, tr)
    dispatches = [s for s in spans if s[0] == DISPATCH
                  and "step" in s[3] and "program" in s[3]]
    if not modules or not dispatches or (lo, hi) not in windows:
        return None
    programs = {s[3]["program"] for s in dispatches}

    def runs_a_step(name) -> bool:
        m = PROGRAM.match(name)
        return bool(m) and m.group(1) in programs

    runs = [(a, b, name) for a, b, name in modules if runs_a_step(name)]
    fetch_of = {s[3]["step"]: s for s in spans
                if s[0] == FETCH and "step" in s[3]}
    span_of = {s[3]["step"]: s[3] for s in spans
               if s[0] in STEP_SPANS and "step" in s[3]}
    # the steps dispatched and fetched inside the window: the rest are
    # cut by one of its ends
    whole = [(i, opened, fetch_of[args["step"]][2], args)
             for i, (_, opened, _, args) in enumerate(dispatches)
             if opened >= lo and args["step"] in fetch_of
             and fetch_of[args["step"]][2] <= hi]
    sound, clock = max((join(whole, runs, first, lo)
                        for first in range(LEADING)),
                       key=lambda found: len(found[0]))

    busy = tr.busy(0)
    took = busy_inside(busy, [(start, end) for _, _, start, end, _ in sound])
    idle = busy_inside(busy, [(ended, start)
                              for _, _, start, _, ended in sound])
    steps = [Step(id=args["step"], kind=args.get("kind"),
                  bucket=args.get("bucket", 0),
                  chunk_start=args.get("chunk_start", 0),
                  rows=args.get("rows"), start=start, end=end,
                  busy_ns=busy_ns, idle_before_ns=start - ended - between,
                  args={**span_of.get(args["step"], {}), **args})
             for (_, args, start, end, ended), busy_ns, between
             in zip(sound, took, idle)]

    joined = {(s.start, s.end) for s in steps}
    shared = {"steps": 0.0, "cut": 0.0, "between": 0.0}
    inside = [(a, b, name) for a, b, name in modules if b > lo and a < hi]
    for (a, b, name), ns in zip(inside, busy_inside(
            busy, [(max(a, lo), min(b, hi)) for a, b, _ in inside])):
        shared["between" if not runs_a_step(name) else
               "steps" if (a, b) in joined else "cut"] += ns

    starts, gaps = [s.start for s in steps], []
    for a, b in sorted(trace.gaps(busy, lo, hi),
                       key=lambda g: g[0] - g[1])[:GAPS_SHOWN]:
        at = bisect.bisect_left(starts, a)
        host = innermost(spans, (a + b) / 2 + sum(clock) / 2)
        gaps.append(Gap(
            b - a, a - lo, steps[at] if at < len(steps) else None,
            " ".join(filter(None, [host[0], host[3].get("part")]))
            if host else "outside", host[3].get("step") if host else None))
    return Record(steps, len(whole), shared, trace.total(busy), clock, gaps,
                  iterations_in(spans, lo, hi))


def record(run):
    """The run's Record, read once from the xplane file that `run.trace`
    was read from, its tables printed; None where there is nothing to
    read."""
    if not hasattr(run, "device_steps"):
        import jax

        run.device_steps = read(
            jax.profiler.ProfileData.from_file(
                trace.newest_xplane(run.ctx.trace_dir)), run.trace)
        if run.device_steps is not None:
            show(run.device_steps)
    return run.device_steps


def steps(run):
    """[Step] of the steps whose dispatch span, fetch span and device
    interval lie wholly inside the window, or None."""
    found = record(run)
    return found.steps if found else None


def by_kind(found: Record) -> dict:
    """{(kind, bucket): [each such Step]}."""
    out = {}
    for s in found.steps:
        out.setdefault((s.kind, s.bucket), []).append(s)
    return out


def show(found: Record) -> None:
    print(f"[steps] {len(found.steps)} steps joined to a device interval "
          f"of {found.dispatched} dispatched and fetched inside the window")
    for (kind, bucket), of in sorted(by_kind(found).items()):
        ms = [s.ms for s in of]
        print(f"[steps] device ms a step, {kind}"
              + (f" bucket {bucket}" if kind != "decode" else "")
              + f": median {harness.median(ms):.3f}, 90th percentile "
              f"{harness.percentile(ms, 90):.3f}, {len(ms)} steps; idle "
              f"before one, mean "
              f"{sum(s.idle_before_ns for s in of) / len(of) / 1e6:.3f}")
    shared, busy_ns = found.busy_ns, found.window_busy_ns
    print("[steps] chip 0's busy s inside the window: "
          + ", ".join(f"{k} {v / 1e9:.4f}" for k, v in shared.items())
          + f"; together {sum(shared.values()) / 1e9:.4f} of "
          f"{busy_ns / 1e9:.4f} ({100 * sum(shared.values()) / busy_ns:.2f}"
          f" %)")
    print(f"[steps] the host's clock is ahead of chip 0's by "
          f"{found.clock_ns[0] / 1e3:.1f} to {found.clock_ns[1] / 1e3:.1f} "
          f"us, or a joined step is not causal; a gap's host span is "
          f"looked up at the middle")
    for gap in found.gaps:
        step = gap.then
        then = (f"step {step.id} {step.kind}"
                + (f" bucket {step.bucket} at {step.chunk_start}"
                   if step.kind != "decode" else "")
                if step else "no joined step")
        print(f"[steps] idle {gap.ns / 1e6:.3f} ms at {gap.at_ns / 1e6:.1f} "
              f"ms, before {then}; the host in {gap.host}"
              + (f" of step {gap.host_step}"
                 if gap.host_step is not None else ""))


def sound(run):
    """The Record where every step dispatched and fetched inside the
    window was joined, else None: the readers of a step's time say
    nothing over a join that is at fault."""
    found = record(run)
    if found and found.dispatched and len(found.steps) == found.dispatched:
        return found
    return None


def step_ms(run, kind: str):
    """Median device milliseconds of the window's steps of `kind`."""
    found = sound(run)
    ms = [s.ms for s in found.steps if s.kind == kind] if found else []
    return harness.median(ms) if ms else None
