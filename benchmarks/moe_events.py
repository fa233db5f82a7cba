"""The expert layer's device events in a traced run, for the `moe_*`
readers.

The program wraps the layer's four phases in `jax.named_scope`s
(`moe.route`, `moe.dispatch`, `moe.experts`, `moe.combine`; ops/moe.py),
and XLA carries the scope path of an instruction's source into its
metadata (`op_name`: forward `.../jvp(l0_moe)/moe.experts/...`, backward
`.../transpose(jvp(l0_moe))/moe.experts/...`; a fusion has its root's). A
TPU trace of this installation does not hold that metadata: an event of
the `XLA Ops` line is named by the instruction's text up to its operands,
and its stats are offsets and durations (looked at by hand, PR 27). So a
traced run of the job compiles the step's text once in set-up
(jobs/train_moe_lm.py::scoped_instructions) and leaves
`[instruction name, scope]` pairs among its counters; the events are
matched to them by name. The grouped matmuls are found by their own
names: the program's Pallas calls are `gmm.<n>` (forward and dX) and
`tgmm.<n>` (dW), after the functions of JAX's megablox that it calls, and
where it takes `jax.lax.ragged_dot` instead XLA lowers that to Mosaic
kernels of its own, `ragged-dot-<kind>.<n>`, whose metadata holds no scope. A run that left no
such pairs (a job or a parent commit without them) has nothing to read
and the readers return None.
"""

from __future__ import annotations

import re

from benchmarks import trace

SCOPE = re.compile(r"moe\.(route|dispatch|experts|combine)")
# kernels/grouped_matmul.py's two paths, and a kernel of the program's own
# by the name the issue reserved for one (`grouped_matmul*`)
GROUPED_MATMUL = re.compile(r"^((gmm|tgmm)(\.|$)|ragged-dot|grouped_matmul)")
INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%(\S+) = .*metadata=\{[^}]*op_name="([^"]*)"', re.M)


def scoped_instructions(hlo_text: str) -> list:
    """[[instruction name, scope]] of the compiled step's instructions
    whose metadata lies inside one of the expert layer's scopes."""
    found = []
    for name, op_name in INSTRUCTION.findall(hlo_text):
        m = SCOPE.search(op_name)
        if m:
            found.append([name, m.group(1)])
    return found


def is_grouped_matmul(name: str) -> bool:
    return bool(GROUPED_MATMUL.match(name))


def events(run) -> list:
    """[(instruction name, scope, start_ns, end_ns)] of chip 0's events in
    the window that belong to the expert layer."""
    pairs = run.result["counters"].get("moe_instructions")
    if not pairs:
        return []
    scope = dict(map(tuple, pairs))
    lo, hi = run.trace.window
    found = []
    for text, a, b in run.trace.chips[0].ops:
        name = trace.op_name(text)
        of = scope.get(name) or ("experts" if is_grouped_matmul(name)
                                 else None)
        if of and min(b, hi) > max(a, lo):
            found.append((name, of, max(a, lo), min(b, hi)))
    return found


def per_step_ms(run, match):
    """Device milliseconds a step in the expert layer's events whose
    (instruction name, scope) `match` accepts; union, so overlap counts
    once."""
    steps = run.result["counters"].get("steps")
    took = trace.total(trace.union(
        (a, b) for name, of, a, b in events(run) if match(name, of))) / 1e9
    return took / steps * 1e3 if steps and took else None
