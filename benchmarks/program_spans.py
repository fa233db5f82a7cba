"""The program's own spans in a traced run, for the per-layer readers.

`flexflow_tpu.telemetry.span(name, **args)` is a
`jax.profiler.TraceAnnotation` named `ff/<name>`: with the profiler on it
is an event of the `/host:CPU` plane, on the clock of the device planes,
and its scalar arguments are the event's stats. A program without such
spans (a parent commit from before them) leaves every function here with
nothing to read: `spans` is empty, the sums are 0 and the readers return
None.

Idle time is charged to a span only where the host and the device take
turns (the serving loop: every iteration ends in a blocking fetch). The
eager fit loop runs ahead of the device, so there a gap between two device
operations is not caused by the span the host happens to be in.
"""

from __future__ import annotations

from benchmarks import trace

PREFIX = "ff/"


def overlap(xs, ys) -> list:
    """The [start, end) pieces that two lists of merged intervals share."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            out.append((lo, hi))
        if xs[i][1] <= ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def read(profile, window) -> list:
    """[(name, start_ns, end_ns, stats)], by start: the profile's `ff/`
    host events clipped to the window, stats as a dict. Empty unless the
    profile's own `bench/window` span is `window`: spans from another
    trace than the device events they are held against are on another
    clock."""
    found, windows = [], []
    lo, hi = window
    for plane in profile.planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                a, b = e.start_ns, e.start_ns + e.duration_ns
                if e.name == trace.WINDOW_SPAN:
                    windows.append((a, b))
                elif e.name.startswith(PREFIX) and min(b, hi) > max(a, lo):
                    found.append((e.name, max(a, lo), min(b, hi),
                                  dict(e.stats)))
    if tuple(window) not in windows:
        return []
    return sorted(found, key=lambda s: (s[1], -s[2]))


def spans(run) -> list:
    """The run's program spans, read once from the xplane file that
    `run.trace` was read from."""
    if not hasattr(run, "program_spans"):
        import jax

        run.program_spans = read(
            jax.profiler.ProfileData.from_file(
                trace.newest_xplane(run.ctx.trace_dir)),
            run.trace.window)
    return run.program_spans


def named(run, *names) -> list:
    return [s for s in spans(run) if s[0] in names]


def count(run, name: str) -> int:
    return len(named(run, name))


def seconds_in(run, names) -> float:
    """Host seconds inside spans of these names (overlap counts once)."""
    return trace.total(trace.union(
        (a, b) for _, a, b, _ in named(run, *names))) / 1e9


def idle_by_span(run) -> dict:
    """{name: seconds} of chip 0's idle time inside the window, each gap
    cut where a program span starts or ends and each piece given to the
    innermost (shortest) span over it; `outside` where there is none.
    The values add up to the window's idle seconds."""
    if not hasattr(run, "program_idle"):
        all_spans = spans(run)
        cuts = sorted({*run.trace.window, *(t for _, a, b, _ in all_spans
                                            for t in (a, b))})
        under = {}
        for a, b in zip(cuts, cuts[1:]):
            over = [s for s in all_spans if s[1] <= a and b <= s[2]]
            name = (min(over, key=lambda s: s[2] - s[1])[0] if over
                    else "outside")
            under.setdefault(name, []).append((a, b))
        idle = trace.gaps(run.trace.busy(0), *run.trace.window)
        run.program_idle = {
            name: trace.total(overlap(idle, trace.union(pieces))) / 1e9
            for name, pieces in under.items()}
    return run.program_idle


def idle_under(run, names) -> float:
    """Seconds chip 0 was idle while the innermost program span was one
    of these."""
    by = idle_by_span(run)
    return sum(by.get(name, 0.0) for name in names)


def device_seconds_while(run, match, alone: bool = False) -> float:
    """Seconds of the window in which an event of chip 0 that `match`
    accepts is open (ops or async line); with `alone`, only those in
    which no other event of the ops line runs."""
    chip = run.trace.chips[0]
    hit = trace.clip(trace.union(
        (a, b) for name, a, b in chip.ops + chip.async_ops
        if match(trace.op_name(name))), *run.trace.window)
    if alone:
        others = trace.union((a, b) for name, a, b in chip.ops
                             if not match(trace.op_name(name)))
        hit = overlap(hit, trace.gaps(others, *run.trace.window))
    return trace.total(hit) / 1e9


def engine_idle_ms(run, phases):
    """Chip 0's idle milliseconds an engine iteration while the innermost
    program span is one of `phases`: what the three `engine_idle_ms.*`
    readers share."""
    iterations = count(run, "ff/serve.iteration")
    if not iterations:
        return None
    return idle_under(run, phases) / iterations * 1e3


def decode_kernel_steps(run) -> list:
    """The spans of the iterations that ran the paged decode kernel:
    every `ff/serve.step` (pure decode), and the `ff/serve.prefill` whose
    chunk is one token (q = 1 is the decode program too; a wider chunk
    takes the gather-and-einsum path and runs no kernel)."""
    return [s for s in named(run, "ff/serve.step", "ff/serve.prefill")
            if s[0] == "ff/serve.step" or s[3].get("tokens") == 1]


def decode_kernel_seconds(run) -> float:
    return run.trace.seconds_of(
        lambda name: name.startswith("flash_attention_paged_decode"))
