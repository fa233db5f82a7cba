"""Start-up as one record: every phase between importing the package and
the first step, and every program JAX traces, lowers and builds.

One process-wide `Tracer` (tracer.py), session-less and always on, capped
at `CAP` events on `time.perf_counter`. It is written on the cold path
only:

- `telemetry.phase(name, **args)` (telemetry/__init__.py) is
  `telemetry.span` plus the same interval into this record, for call
  sites that run a bounded number of times a process (compile, serve(),
  a restore). `telemetry.span` never touches the record.
- one listener on `jax.monitoring`, registered when this module is
  imported, turns the three durations JAX reports a build into completed
  events on the thread that built: `build.trace` (a function to a
  jaxpr), `build.lower` (the jaxpr to an MLIR module) and
  `build.backend` (the compile, or the read from the persistent cache:
  then with `cache_read_s`), each with `program`, the function's name
  (`decode_step`; JAX reports the lower and the backend as
  `jit(decode_step)`, one form is kept). An event ends at the callback
  and starts its duration earlier. Trace events NEST (the functions a
  program calls under `jit` report their own inside the outer one's), so
  seconds of tracing are the union of the events on a thread, never
  their sum, and a program's count of builds is its count of
  `build.backend`. A step that runs a program already built reports
  nothing, so the listener is silent in steady state.

A build of a program that was built before, once steps have begun
(`steps_began`: `fit` and the serving engine's first dispatch say so),
is a recompile: it is logged once a program, at warning level, with its
three durations.

Read side: `events()` gives `[(name, t0, t1, thread id, args)]` on
`perf_counter`, `dropped()` the events lost to the cap, `dump(path)` the
Chrome JSON (a closing session writes `startup_trace.json`), `summary()`
what the fit summary carries. Nesting is by thread and containment and
is the reader's to compute: a phase's parent is the innermost phase of
its thread that contains it (docs/observability.md, "Start-up").
"""

from __future__ import annotations

import heapq
import re
import threading
import time

import jax.monitoring

from .. import _IMPORT_T0
from . import log
from .tracer import Tracer

CAP = 65536
TRACE, LOWER, BACKEND = "build.trace", "build.lower", "build.backend"
_BUILD_OF = {
    "/jax/core/compile/jaxpr_trace_duration": TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
    "/jax/core/compile/backend_compile_duration": BACKEND,
}
# reported inside the backend's duration, on a hit of the persistent cache
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_WRAPPED = re.compile(r"^\w+\((.*)\)$")   # jit(decode_step)

_record = Tracer(max_events=CAP, t0=_IMPORT_T0)
_lock = threading.Lock()
_built: dict[str, int] = {}     # program -> its builds so far
_rebuilt_logged: set[str] = set()
_stepping = False
_thread = threading.local()     # of the build in progress on this thread


def complete(name: str, t0: float, t1: float, args=None) -> None:
    """One interval on `perf_counter` into the record."""
    _record.complete(name, t0, t1, **(args or {}))


def steps_began() -> None:
    """From here on a build of a program built before is a recompile."""
    global _stepping
    _stepping = True


def _on_duration(event: str, secs: float, fun_name=None, **_kw) -> None:
    kind = _BUILD_OF.get(event)
    if kind is None:
        if event == _CACHE_READ:
            _thread.cache_read_s = secs
        return
    now = time.perf_counter()
    wrapped = _WRAPPED.match(fun_name or "")
    program = wrapped.group(1) if wrapped else str(fun_name)
    args = {"program": program}
    parts = _thread.__dict__.setdefault("parts", {})
    if kind != BACKEND:
        parts[kind, program] = secs
    else:
        read_s = _thread.__dict__.pop("cache_read_s", None)
        if read_s is not None:
            args["cache_read_s"] = read_s
        _note_build(program, parts.get((TRACE, program), 0.0),
                    parts.get((LOWER, program), 0.0), secs)
        parts.clear()
    _record.complete(kind, now - secs, now, **args)


def _note_build(program: str, trace_s: float, lower_s: float,
                backend_s: float) -> None:
    with _lock:
        builds = _built[program] = _built.get(program, 0) + 1
        if builds == 1 or not _stepping or program in _rebuilt_logged:
            return
        _rebuilt_logged.add(program)
    log.warning(
        "startup: %s was built again after steps began (build %d: trace "
        "%.3f s, lower %.3f s, backend %.3f s): a new shape or a new "
        "static argument recompiled it", program, builds, trace_s, lower_s,
        backend_s)


jax.monitoring.register_event_duration_secs_listener(_on_duration)


# ------------------------------------------------------------------ read

def events() -> list[tuple]:
    """`[(name, t0, t1, thread id, args)]` on `perf_counter`, in the
    order the intervals ended."""
    return _record.intervals()


def dropped() -> int:
    """Events lost to the cap."""
    return _record.dropped


def dump(path: str) -> str:
    """The record as Chrome trace-event JSON (Perfetto opens it)."""
    return _record.dump(path)


def self_seconds(intervals) -> list[float]:
    """Of each `(t0, t1)` of one thread, the seconds that no shorter one
    of them covers: an interval's self time where they nest, whatever
    the clocks did to their edges."""
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
    """Seconds that at least one of the `(t0, t1)` covers."""
    total, end = 0.0, float("-inf")
    for t0, t1 in sorted(intervals):
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return total


def summary() -> dict:
    """What the fit summary says of start-up: each phase's self time
    (its seconds under no phase inside it and no build of its thread),
    summed by name, and the three kinds of build, each the union of its
    events on a thread summed over threads; the builds counted and the
    drops."""
    by_thread: dict[int, list] = {}
    for ev in events():
        by_thread.setdefault(ev[3], []).append(ev)
    phases: dict[str, float] = {}
    builds = {TRACE: 0.0, LOWER: 0.0, BACKEND: 0.0}
    programs = 0
    for evs in by_thread.values():
        own = self_seconds([(t0, t1) for _, t0, t1, _, _ in evs])
        for (name, *_), secs in zip(evs, own):
            if name not in builds:
                phases[name] = phases.get(name, 0.0) + secs
        for kind in builds:
            builds[kind] += union_seconds(
                [(t0, t1) for name, t0, t1, _, _ in evs if name == kind])
        programs += sum(name == BACKEND for name, *_ in evs)
    return {
        "startup_phase_self_s": {k: round(v, 6) for k, v in phases.items()},
        "startup_build_s": {k.split(".")[1]: round(v, 6)
                            for k, v in builds.items()},
        "startup_programs": programs,
        "startup_dropped_events": dropped(),
    }
