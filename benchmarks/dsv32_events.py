"""The device events of sparse latent attention and of the expert layer in
a traced serving run, for the `dsa_index_ms`, `mla_attend_ms` and `moe_ms`
readers.

The program wraps the parts of a layer in `jax.named_scope`s (`mla.q`,
`mla.kv`, `dsa.index`, `dsa.topk`, `mla.attend`, `mla.out`:
ops/latent_attention.py; `moe.route`, `moe.dispatch`, `moe.experts`,
`moe.combine`, `moe.shared`: ops/moe.py). A TPU trace names an event by
its instruction and holds no scope (benchmarks/moe_events.py), so a traced
run of the job compiles the text of the engine's pure-decode step once in
set-up and leaves `[instruction name, scope]` pairs among its counters
(`decode_instructions`). Instruction names are one program's: the events
read are those inside the `ff/serve.step` spans, the iterations that only
decode, and the readers give milliseconds a such iteration. A run that
left no pairs, or a program without the spans (a parent commit), has
nothing to read and the readers return None.
"""

from __future__ import annotations

import re

from benchmarks import moe_events, program_spans, trace

SCOPE = re.compile(r"(mla\.(?:q|kv|attend|out)|dsa\.(?:index|topk)"
                   r"|moe\.(?:route|dispatch|experts|combine|shared))")
INDEX = ("dsa.index", "dsa.topk")
ATTEND = ("mla.attend",)
MOE = ("moe.route", "moe.dispatch", "moe.experts", "moe.combine",
       "moe.shared")


def scoped_instructions(hlo_text: str) -> list:
    """[[instruction name, scope]] of a compiled step's instructions whose
    metadata lies inside one of the scopes above (the innermost)."""
    found = []
    for name, op_name in moe_events.INSTRUCTION.findall(hlo_text):
        scopes = SCOPE.findall(op_name)
        if scopes:
            found.append([name, scopes[-1]])
    return found


def by_scope(run) -> dict:
    """{scope: device seconds} of chip 0's events inside the pure-decode
    iterations, `other` for those no scope claims, and `steps`, the
    number of those iterations."""
    if hasattr(run, "dsv32_by_scope"):
        return run.dsv32_by_scope
    pairs = run.result["counters"].get("decode_instructions")
    steps = program_spans.named(run, "ff/serve.step")
    run.dsv32_by_scope = out = {}
    if not pairs or not steps:
        return out
    scope = dict(map(tuple, pairs))
    inside = trace.union((a, b) for _, a, b, _ in steps)
    events = {}
    for text, a, b in run.trace.chips[0].ops:
        name = trace.op_name(text)
        of = scope.get(name) or ("moe.experts"
                                 if moe_events.is_grouped_matmul(name)
                                 else "other")
        events.setdefault(of, []).append((a, b))
    for of, spans in events.items():
        out[of] = trace.total(program_spans.overlap(
            trace.union(spans), inside)) / 1e9
    out["steps"] = len(steps)
    print("[dsv32] device ms a pure-decode iteration by scope: "
          + ", ".join(f"{k} {v / len(steps) * 1e3:.3f}"
                      for k, v in sorted(out.items()) if k != "steps")
          + f" ({len(steps)} iterations)")
    return out


def per_step_ms(run, scopes):
    found = by_scope(run)
    took = sum(found.get(s, 0.0) for s in scopes)
    return took / found["steps"] * 1e3 if found and took else None
