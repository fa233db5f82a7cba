"""The device events of an LFM2-MoE training step in a traced run, for the
readers of `lfm2-train-8k`.

The program wraps the phases of its layers in `jax.named_scope`s: the short
convolution's `sconv.proj`, `sconv.conv`, `sconv.out` (ops/short_conv.py),
a grouped attention layer's `gqa.qkv`, `gqa.repeat`, `gqa.attend`, `gqa.out`
(ops/attention.py) and the expert layer's `moe.*` (ops/moe.py). A TPU trace
of this installation names an event by its instruction and holds no scope
(moe_events.py says how that was found), so a traced run of the job
compiles the step's text once in set-up and leaves `[instruction name,
scope]` pairs among its counters (`lfm2_instructions`); the events are
matched to them by name. `scoped_instructions` is moe_events.py's with the
pattern as an argument and the whole scope kept (`sconv.proj`, not `proj`):
the accepted file is not edited. The grouped matmuls and the flash kernels
are found by their own names, as the accepted readers find them. A run
that left no pairs (another job, or a parent commit without the scopes)
has nothing to read and every function here returns None or nothing.
"""

from __future__ import annotations

import re

from benchmarks import moe_events, trace

SCOPES = r"(sconv\.(?:proj|conv|out)|gqa\.(?:qkv|repeat|attend|out)|moe\.(?:route|dispatch|experts|combine))"


def scoped_instructions(hlo_text: str, pattern: str = SCOPES) -> list:
    """[[instruction name, scope]] of the compiled step's instructions
    whose metadata lies inside a scope `pattern` (one group) matches."""
    scope = re.compile(pattern)
    found = []
    for name, op_name in moe_events.INSTRUCTION.findall(hlo_text):
        m = scope.search(op_name)
        if m:
            found.append([name, m.group(1)])
    return found


def is_family(run) -> bool:
    """Whether the run is of this family: its configuration has
    `layer_types` with a `conv` layer and the job left its pairs."""
    return ("conv" in run.config.get("layer_types", ())
            and bool(run.result["counters"].get("lfm2_instructions")))


def events(run, prefix: str) -> list:
    """[(instruction name, scope, start_ns, end_ns)] of chip 0's events in
    the window whose scope starts with `prefix`."""
    if not is_family(run):
        return []
    scope = dict(map(tuple, run.result["counters"]["lfm2_instructions"]))
    lo, hi = run.trace.window
    found = []
    for text, a, b in run.trace.chips[0].ops:
        name = trace.op_name(text)
        of = scope.get(name)
        if of and of.startswith(prefix) and min(b, hi) > max(a, lo):
            found.append((name, of, max(a, lo), min(b, hi)))
    return found


def per_step_ms(run, intervals):
    """Device milliseconds a step in `intervals` ((start_ns, end_ns), ..);
    union, so overlap counts once. None where there is nothing."""
    steps = run.result["counters"].get("steps")
    took = trace.total(trace.union(intervals)) / 1e9
    return took / steps * 1e3 if steps and took else None


def scope_ms(run, prefix: str):
    return per_step_ms(run, ((a, b) for _, _, a, b in events(run, prefix)))


def named_ms(run, match):
    """Device milliseconds a step in chip 0's events whose instruction
    name `match` accepts (the kernels that carry their own names)."""
    if not is_family(run):
        return None
    lo, hi = run.trace.window
    return per_step_ms(run, (
        (max(a, lo), min(b, hi)) for text, a, b in run.trace.chips[0].ops
        if match(trace.op_name(text)) and min(b, hi) > max(a, lo)))
