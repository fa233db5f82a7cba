"""Set-up as the program's own record of it, cut to the run's set-up.

The program keeps one record of its cold path
(`flexflow_tpu/telemetry/startup.py`, docs/observability.md "Start-up"):
the phases between importing the package and the first step, by name,
and every program JAX traced (`build.trace`), lowered (`build.lower`) and
built or read from the compile cache (`build.backend`), each an interval
on `time.perf_counter` with the thread that ran it. That is the clock of
`ctx.t_start`, `ctx.spans` and `ctx.window`, so this module cuts the
record to `[ctx.t_start, ctx.window[0]]`, the interval whose length is
`setup_s`, and hands the readers of `setup_import_s`, `setup_search_s`,
`setup_weights_s`, `setup_trace_s`, `setup_programs` and
`setup_unnamed_s` their numbers. An interval that straddles an end of
the cut counts the part inside it.

Nesting is by thread and containment and is computed here: on one thread
an interval's self time is its length less what the shorter intervals of
that thread cover inside it. Trace events nest (a function traced under
`jit` reports its own inside the outer one's), so seconds of a kind of
build are the union of its events on a thread, summed over threads as
the harness's `xla_compile_s` sums the backend's.

In a traced run the record is printed as a table: each harness span of
set-up in order, and the time before the first of them, with the
program's phases inside it by self time, the builds' seconds (each
kind's own, less the builds inside it) and what is left; the ten
programs with the most build seconds; the record's checks of itself
(`build.backend` against the harness's own listener, the roots against
`ffcompile`, the hole, the drops).

A program that keeps no record (every commit before PR 51) has no read
side: `record` is None and so is every reader.
"""

from __future__ import annotations

import dataclasses
import heapq
import threading
from typing import Optional

TRACE, LOWER, BACKEND = "build.trace", "build.lower", "build.backend"
BUILDS = (TRACE, LOWER, BACKEND)
ROOTS = ("compile", "serve.compile")
# what FlexFlow's own planning costs a start
SEARCH = ("warmstart.plan_lookup", "warmstart.calibration_load",
          "warmstart.store", "compile.calibrate", "compile.search",
          "compile.update_sharding", "compile.verify")
WEIGHTS = ("compile.init", "serve.adopt")
IMPORT = "import"
PROGRAMS_SHOWN = 10
SHOWN_FROM_S = 0.005     # a table's line leaves out what took less


@dataclasses.dataclass
class Record:
    events: list        # (name, t0, t1, thread id, args), cut to [lo, hi]
    dropped: int        # events the program's record lost to its cap
    main: int           # the thread the job ran on
    lo: float
    hi: float
    late: list          # the builds that ended inside the window, uncut


def read_side():
    """The program's start-up record (its module), or None where the
    program keeps none."""
    from flexflow_tpu import telemetry

    return getattr(telemetry, "startup", None)


def cut(events, lo: float, hi: float) -> list:
    """The events that reach into [lo, hi], clipped to it."""
    return [(name, max(t0, lo), min(t1, hi), tid, args)
            for name, t0, t1, tid, args in events if t1 > lo and t0 < hi]


def self_seconds(intervals) -> list:
    """Of each (t0, t1) of one thread, the seconds that no shorter one of
    them covers."""
    own = [0.0] * len(intervals)
    edges = sorted({t for iv in intervals for t in iv})
    starting = sorted(range(len(intervals)), key=lambda i: intervals[i][0])
    live, k = [], 0     # (length, end, index): the shortest on top
    for a, b in zip(edges, edges[1:]):
        while k < len(starting) and intervals[starting[k]][0] <= a:
            i = starting[k]
            t0, t1 = intervals[i]
            heapq.heappush(live, (t1 - t0, t1, i))
            k += 1
        while live and live[0][1] <= a:
            heapq.heappop(live)
        if live:
            own[live[0][2]] += b - a
    return own


def union_seconds(intervals) -> float:
    """Seconds that at least one of the (t0, t1) covers."""
    total, end = 0.0, float("-inf")
    for t0, t1 in sorted(intervals):
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total


def by_thread(events) -> dict:
    out = {}
    for ev in events:
        out.setdefault(ev[3], []).append(ev)
    return out


def union_of(rec: Record, names, main_only: bool) -> float:
    """Seconds under the events named, the union on a thread, over the
    job's thread or summed over all."""
    return sum(
        union_seconds([(t0, t1) for name, t0, t1, _, _ in evs
                       if name in names])
        for tid, evs in by_thread(rec.events).items()
        if not main_only or tid == rec.main)


# ------------------------------------------------------------- the metrics

def import_s(rec: Record) -> float:
    return union_of(rec, (IMPORT,), main_only=False)


def search_s(rec: Record) -> float:
    return union_of(rec, SEARCH, main_only=True)


def weights_s(rec: Record) -> float:
    return union_of(rec, WEIGHTS, main_only=True)


def trace_s(rec: Record) -> float:
    return union_of(rec, (TRACE, LOWER), main_only=False)


def programs(rec: Record) -> float:
    """Backend builds (compiles or cache reads) that ended in set-up."""
    return float(sum(name == BACKEND and t1 < rec.hi
                     for name, _, t1, _, _ in rec.events))


def unnamed_s(rec: Record) -> float:
    """Seconds of `compile` and `serve.compile` under no phase inside
    them and no build of their thread (a `compile` under `serve.graph`
    counts its own)."""
    total = 0.0
    for evs in by_thread(rec.events).values():
        own = self_seconds([(t0, t1) for _, t0, t1, _, _ in evs])
        total += sum(s for (name, *_), s in zip(evs, own) if name in ROOTS)
    return total


def root_seconds(rec: Record) -> float:
    """Seconds under `compile` or `serve.compile`, a nested one once."""
    return union_of(rec, ROOTS, main_only=False)


# --------------------------------------------------------------- the table

def setup_spans(ctx, lo: float, hi: float) -> list:
    """The harness's spans of set-up, outermost only, in order, as
    (name, start, end); before them `(before)`, from `lo` to the first."""
    inside = sorted(((a, -b, n) for n, a, b in ctx.spans
                     if a >= lo and b <= hi))
    out, end = [], lo
    for a, neg_b, name in inside:
        if a >= end:
            out.append((name, a, -neg_b))
            end = -neg_b
    if out and out[0][1] > lo:
        out.insert(0, ("(before)", lo, out[0][1]))
    return out


def inside_of(rec: Record, a: float, b: float) -> dict:
    """What the record holds of [a, b]: `phases` {name: self seconds on
    the job's thread}, `builds` {kind: its own seconds there, less the
    builds inside it}, `others` {kind: union on the other threads},
    `left`: seconds of the job's thread under nothing."""
    threads = by_thread(cut(rec.events, a, b))
    phases, builds = {}, dict.fromkeys(BUILDS, 0.0)
    mine = threads.pop(rec.main, [])
    own = self_seconds([(t0, t1) for _, t0, t1, _, _ in mine])
    for (name, *_), secs in zip(mine, own):
        if name in builds:
            builds[name] += secs
        else:
            phases[name] = phases.get(name, 0.0) + secs
    others = {kind: sum(union_seconds([(t0, t1) for n, t0, t1, _, _ in evs
                                       if n == kind])
                        for evs in threads.values()) for kind in BUILDS}
    return {"phases": phases, "builds": builds, "others": others,
            "left": (b - a) - sum(own)}


def by_program(events) -> list:
    """(build seconds, builds, program) of each program, the most
    seconds first: the lengths of its trace, lower and backend events,
    and its count of backend events."""
    found = {}
    for name, t0, t1, _, args in events:
        if name in BUILDS:
            entry = found.setdefault(args.get("program"), [0.0, 0])
            entry[0] += t1 - t0
            entry[1] += name == BACKEND
    return sorted(((s, n, p) for p, (s, n) in found.items()),
                  key=lambda e: -e[0])


def _kinds(seconds: dict) -> str:
    return " ".join(f"{k.split('.')[1]} {seconds[k]:.2f}" for k in BUILDS)


def show(rec: Record, ctx) -> None:
    print(f"[setup] the program's record of {rec.hi - rec.lo:.2f} s of "
          f"set-up: {len(rec.events)} events, {rec.dropped} dropped")
    for name, a, b in setup_spans(ctx, rec.lo, rec.hi):
        found = inside_of(rec, a, b)
        phases = sorted(found["phases"].items(), key=lambda p: -p[1])
        line = f"[setup] {name} {b - a:.2f} s:"
        line += "".join(f" {n} {s:.2f}," for n, s in phases
                        if s >= SHOWN_FROM_S)
        line += f" builds {_kinds(found['builds'])}, left {found['left']:.2f}"
        if sum(found["others"].values()) >= SHOWN_FROM_S:
            line += f"; other threads' builds {_kinds(found['others'])}"
        print(line)
    for secs, builds, program in by_program(rec.events)[:PROGRAMS_SHOWN]:
        print(f"[setup] {program}: {secs:.2f} build s, {builds} builds")
    hits = [args["cache_read_s"] for name, _, _, _, args in rec.events
            if name == BACKEND and "cache_read_s" in args]
    backend = sum(t1 - t0 for name, t0, t1, _, _ in rec.events
                  if name == BACKEND and t1 < rec.hi)
    print(f"[setup] build.backend {backend:.3f} s in {programs(rec):.0f} "
          f"programs ({len(hits)} read from the compile cache in "
          f"{sum(hits):.3f} s); the harness's listener heard "
          f"{ctx.xla_compile_setup_s:.3f} s")
    roots, ffcompile = root_seconds(rec), sum(ctx.seconds_in("ffcompile"))
    print(f"[setup] compile and serve.compile {roots:.2f} s of ffcompile's "
          f"{ffcompile:.2f}: {ffcompile - roots:.2f} s left for the "
          f"layers' construction; {unnamed_s(rec):.2f} s of the roots "
          f"under no phase and no build; weights: "
          + ", ".join(f"{n} {union_of(rec, (n,), True):.2f}"
                      for n in WEIGHTS)
          + f"; trace {union_of(rec, (TRACE,), False):.2f} s, lower "
          f"{union_of(rec, (LOWER,), False):.2f} s")
    for secs, builds, program in by_program(rec.late):
        print(f"[setup] built inside the window: {program}, {secs:.3f} "
              f"build s")


def read(side, lo: float, hi: float, main: int,
         closed: float = float("inf")) -> Record:
    """The side's record cut to [lo, hi]; `closed`: when the window that
    opened at hi closed."""
    events = side.events()
    return Record(cut(events, lo, hi), side.dropped(), main, lo, hi,
                  [ev for ev in events
                   if ev[0] in BUILDS and hi < ev[2] <= closed])


def record(run) -> Optional[Record]:
    """The run's Record, read once and its table printed; None where the
    program keeps no record."""
    if not hasattr(run, "startup"):
        side = read_side()
        run.startup = None
        if side is not None:
            run.startup = read(side, run.ctx.t_start, run.ctx.window[0],
                               threading.main_thread().ident,
                               run.ctx.window[1])
            show(run.startup, run.ctx)
    return run.startup


def metric(run, of) -> Optional[float]:
    """`of(record)`, or None on a program without a record."""
    rec = record(run)
    return None if rec is None else float(of(rec))
