"""A cell's set-up on the device's clock, or its harness spans alone.

    chiprun -- python scripts/startup_trace.py --workload c13b-serve-chat
    chiprun -- python scripts/startup_trace.py --workload olmoe-train-4k \
        --harness-only --root chiprun_work/6ab37eb --runs 3

The benchmark starts its profiler when the window opens, so the program's
set-up phases (`telemetry.phase`: `ff/` annotations like every span) have
never been on a device timeline. This runs one cell's job as
`benchmarks/run.py --trace 0` does, through a `harness.Context` whose
`open_window` is where THIS script's profiler stops: started before the
job, it holds the harness spans (`bench/`) and the program's phases
(`ff/`) of set-up on the host plane over the device's `XLA Ops` line, and
the script prints the device's busy share of each. `--until NAME` stops
the profiler earlier, when the first harness span after the ones called
NAME opens (a serving cell's first round runs thousands of steps: its
whole set-up may be more events than a trace holds).

Either way the harness's spans of set-up are printed, and under them the
program's own record of the same set-up as benchmarks/startup.py's table
(where the checkout keeps one). `--harness-only` starts no profiler: that
works on any old commit; `--root DIR` runs the benchmark and the program of another
checkout (a `git archive` of an old commit), `--runs N` repeats the job
in processes of their own, one after another.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = os.path.join(os.path.dirname(HERE), ".bench_trace", "startup")


def busy_shares(xplane: str) -> list:
    """[(name, seconds, the seconds of it chip 0 ran an operation, how
    many)] of the `bench/` and `ff/` events of the host plane, by first
    start."""
    import jax

    from benchmarks import program_spans, trace

    profile = jax.profiler.ProfileData.from_file(xplane)
    busy, spans = [], []
    for plane in profile.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == 0:
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    busy = trace.union(
                        (e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name == trace.HOST_PLANE:
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith((trace.SPAN_PREFIX,
                                                program_spans.PREFIX))]
    by_name = {}
    for name, a, b in sorted(spans, key=lambda s: s[1]):
        by_name.setdefault(name, []).append((a, b))
    return [(name, trace.total(trace.union(ivs)) / 1e9,
             trace.total(program_spans.overlap(trace.union(ivs), busy)) / 1e9,
             len(ivs)) for name, ivs in by_name.items()]


def one_run(opts) -> int:
    root = os.path.abspath(opts.root)
    sys.path.insert(0, root)
    from benchmarks import harness, run

    state = {"ctx": None, "tracing": False, "seen": False}

    def stop_profiler():
        if state["tracing"]:
            import jax

            jax.profiler.stop_trace()
            state["tracing"] = False

    class Context(harness.Context):
        def __init__(self, **kw):
            super().__init__(**kw)
            state["ctx"] = self

        def span(self, name):
            if name == opts.until:
                state["seen"] = True
            elif state["seen"]:
                stop_profiler()
            return super().span(name)

        def open_window(self):
            stop_profiler()
            return super().open_window()

    harness.Context = Context
    if not opts.harness_only:
        import shutil

        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", run.CACHE_DIR)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        state["tracing"] = True
    try:
        rc = run.main(["--workload", opts.workload, "--seed", str(opts.seed),
                       "--seconds", str(opts.seconds), "--trace", "0"])
    finally:
        stop_profiler()
    ctx = state["ctx"]
    print(f"[startup] {opts.workload} seed {opts.seed} at {root}: set-up "
          f"{ctx.setup_s:.2f} s")
    at = ctx.t_start
    for name, a, b in sorted(ctx.spans, key=lambda s: s[1]):
        if a >= at and b <= ctx.window[0]:      # outermost, in set-up
            print(f"[startup] {a - at:6.2f} s under no span, then {name} "
                  f"{b - a:.2f} s")
            at = b
    print(f"[startup] {ctx.window[0] - at:6.2f} s under no span, then the "
          f"window")
    try:    # the program's own record of the same set-up, as a table
        from benchmarks import startup as record_reader
    except ImportError:     # a checkout from before PR 51
        record_reader = None
    if record_reader is not None:
        record_reader.record(types.SimpleNamespace(ctx=ctx))
    if not opts.harness_only:
        from benchmarks import trace

        for name, secs, busy, n in busy_shares(trace.newest_xplane(TRACE_DIR)):
            print(f"[startup] {name} x{n}: {secs:.3f} s, chip 0 busy "
                  f"{busy:.3f} s ({100 * busy / secs if secs else 0:.1f} %)")
    return rc


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--harness-only", action="store_true")
    ap.add_argument("--until", default=None,
                    help="stop the profiler after the harness spans of "
                         "this name")
    ap.add_argument("--root", default=os.path.dirname(HERE))
    ap.add_argument("--runs", type=int, default=1)
    opts = ap.parse_args()
    if opts.runs == 1:
        return one_run(opts)
    # a process a run, each with a seed of its own: set-up is what a
    # fresh process pays
    for k in range(opts.runs):
        argv = ["--workload", opts.workload, "--seed", str(opts.seed + k),
                "--seconds", str(opts.seconds), "--root", opts.root]
        argv += ["--harness-only"] if opts.harness_only else []
        argv += ["--until", opts.until] if opts.until else []
        rc = subprocess.run([sys.executable, os.path.abspath(__file__),
                             *argv]).returncode
        if rc:
            return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
