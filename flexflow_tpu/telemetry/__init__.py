"""Run-wide observability: tracer spans, structured metrics, leveled logs.

Four coordinated pieces (docs/observability.md):

1. **Tracer** (tracer.py) — host-side span/counter/instant events dumped as
   Chrome trace-event JSON (Perfetto / chrome://tracing), plus an opt-in
   `jax.profiler.trace` passthrough (`--xprof-dir`) for device timelines.
2. **MetricsRecorder** (recorder.py) — JSONL event log with a run manifest
   and derived rates; `summary` record carries p50/p95 step time.
3. **Instrumentation hooks** — model compile/fit, search/, resilience/,
   dataloader call the module-level `span`/`instant`/`counter`/`event`
   helpers below. They dispatch to the ACTIVE session when one exists and
   cost one global read + one `is None` test when telemetry is off, so the
   hooks can live permanently in hot paths. `span` is also, always, a
   `jax.profiler.TraceAnnotation` named `ff/<name>`: whenever a profiler
   trace is running (`--xprof-dir`, the benchmark's `--trace 1`) the
   program's spans sit on the host plane of that trace, on the device
   timeline's clock; with none running the annotation is inert (about
   half a microsecond).
4. **The start-up record** (startup.py) — session-less and always on:
   `phase` is `span` plus the same interval into one process-wide record
   of the cold path, which also holds every program JAX traces, lowers
   and builds. For call sites that run a bounded number of times a
   process; a step's spans stay `span`.

Enable with `--telemetry-dir DIR` (FFConfig), `model.enable_telemetry(DIR)`,
or the keras `Telemetry` callback; read back via `model.get_telemetry()`.
"""

from __future__ import annotations

import time
from typing import Optional

from jax.profiler import TraceAnnotation

from . import log  # noqa: F401  (flexflow_tpu.telemetry.log)
from . import startup  # the start-up record; registers its build listener
from .metrics import MetricsRegistry  # noqa: F401  (re-export)
from .recorder import MetricsRecorder, read_jsonl
from .session import TelemetrySession
from .tracer import Tracer

# ffscope flight recorder (scope/flightrec.py): stdlib-only, always-on
# bounded ring fed from the dispatchers below.  Its own hot path is the
# same one-global-read discipline — when disabled, _flight.record is a
# global load + `is None` test.
from ..scope import flightrec as _flight

__all__ = [
    "Tracer", "MetricsRecorder", "MetricsRegistry", "TelemetrySession",
    "read_jsonl", "log",
    "activate", "deactivate", "active_session",
    "span", "phase", "startup", "instant", "counter", "event",
    "inc", "observe", "set_gauge",
]

_active: Optional[TelemetrySession] = None
# same-session nesting depth: the disaggregated serving coordinator
# holds one activation across an overlapped step while both engines'
# inner _active() blocks enter and exit on their own threads — an
# unbalanced deactivate must not tear the session down mid-step
_depth: int = 0


# what a span hands the profiler: its name under this prefix, and those of
# its arguments that are scalars (a trace event's stats hold nothing else)
ANNOTATION_PREFIX = "ff/"
_SCALARS = (int, float, bool, str)


class _SessionSpan:
    """A span with a session active: the profiler's annotation outside,
    the session tracer's Chrome-JSON span inside it."""

    __slots__ = ("annotation", "traced")

    def __init__(self, annotation, traced):
        self.annotation = annotation
        self.traced = traced

    def __enter__(self):
        self.annotation.__enter__()
        self.traced.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.traced.__exit__(exc_type, exc, tb)
        self.annotation.__exit__(exc_type, exc, tb)
        return False


def activate(session: TelemetrySession) -> TelemetrySession:
    """Install `session` as the process-wide telemetry sink. Activating
    the session that is already active nests: the sink stays installed
    until the matching number of deactivate(session) calls."""
    global _active, _depth
    if _active is session:
        _depth += 1
    else:
        _active = session
        _depth = 1
    return session


def deactivate(session: Optional[TelemetrySession] = None):
    """Remove the active session (or only `session`, if it is active).
    Same-session activations nest — only the outermost deactivate
    removes the sink; deactivate(None) always tears down."""
    global _active, _depth
    if session is None:
        _active = None
        _depth = 0
    elif _active is session:
        _depth -= 1
        if _depth <= 0:
            _active = None
            _depth = 0


def active_session() -> Optional[TelemetrySession]:
    return _active


# ---------------------------------------------------------------- dispatch
# Hot-path helpers: cheap no-ops when no session is active (a span is
# then one inert trace annotation).

def span(name: str, **args):
    _flight.record("span", name)
    annotation = TraceAnnotation(ANNOTATION_PREFIX + name, **{
        k: v for k, v in args.items() if isinstance(v, _SCALARS)})
    s = _active
    if s is None:
        return annotation
    return _SessionSpan(annotation, s.tracer.span(name, **args))


class _Phase:
    """A span whose interval also goes into the start-up record."""

    __slots__ = ("spanned", "name", "args", "t0")

    def __init__(self, spanned, name, args):
        self.spanned = spanned
        self.name = name
        self.args = args
        self.t0 = 0.0

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.spanned.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.spanned.__exit__(exc_type, exc, tb)
        startup.complete(self.name, self.t0, time.perf_counter(), self.args)
        return False


def phase(name: str, **args):
    """`span`, and the same interval into the start-up record
    (startup.py). Cold path only: compile, serve(), a restore."""
    return _Phase(span(name, **args), name,
                  {k: v for k, v in args.items() if isinstance(v, _SCALARS)})


def instant(name: str, **args):
    _flight.record("instant", name)
    s = _active
    if s is not None:
        s.tracer.instant(name, **args)


def counter(name: str, values: dict):
    _flight.record("counter", name)
    s = _active
    if s is not None:
        s.tracer.counter(name, values)


def event(kind: str, **fields):
    """Structured JSONL record into the active session's metrics log."""
    _flight.record("event", kind, fields.get("step"))
    s = _active
    if s is not None:
        s.recorder.record(kind, **fields)


# ffpulse registry dispatch (metrics.py): same one-global-read no-op
# contract as span/instant — with telemetry off, no registry (and no
# metric object) is ever touched or created.

def inc(name: str, value: float = 1.0, **labels):
    """Counter increment on the active session's registry."""
    s = _active
    if s is not None:
        s.metrics.counter(name, **labels).inc(value)


def observe(name: str, value: float, **labels):
    """Histogram observation on the active session's registry."""
    s = _active
    if s is not None:
        s.metrics.histogram(name, **labels).observe(value)


def set_gauge(name: str, value: float, **labels):
    """Gauge set on the active session's registry."""
    s = _active
    if s is not None:
        s.metrics.gauge(name, **labels).set(value)
