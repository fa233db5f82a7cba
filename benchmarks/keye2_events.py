"""The device events of grouped-KV attention under a learned selection, of
its indexer and of the expert layer in a traced run of
`keye2-serve-mediaqa`, and the functions that count the bytes their
rooflines are held to.

The program wraps the parts of a layer in `jax.named_scope`s (`gsa.qkv`,
`gsa.attend`, `gsa.out`: ops/attention.py, ops/inc_attention.py;
`dsa.index`, `dsa.topk`: the indexer's projections and scores, the top-k;
`moe.route`, `moe.dispatch`, `moe.experts`, `moe.combine`: ops/moe.py). A
TPU trace names an event by its instruction and holds no scope
(benchmarks/moe_events.py), so a traced run of the job compiles the text
of the engine's pure-decode step once in set-up and leaves `[instruction
name, scope]` pairs among its counters (`keye2_instructions`). A step's
events are those inside the device's own interval for it
(`device_steps.sound(run).steps`, kind `decode`: first to last operation
of the step's execution), not inside the host's span, which with a step
in flight lies a little after the device's work (PERF.md section 7); what
only the engine knows of a step (`sel_rows`, `index_rows`, `kv_itemsize`)
comes from the step's own `ff/serve.step` arguments, joined by its id. A
run that left no pairs, a program without the spans or the scopes (a
parent commit), or a join at fault has nothing to read and the readers
return None.
"""

from __future__ import annotations

import bisect
import re

from benchmarks import device_steps, moe_events, trace

SCOPE = re.compile(r"(gsa\.(?:qkv|attend|out)|dsa\.(?:index|topk)"
                   r"|moe\.(?:route|dispatch|experts|combine|shared))")
ATTEND = ("gsa.attend",)
INDEX = ("dsa.index",)


def scoped_instructions(hlo_text: str) -> list:
    """[[instruction name, scope]] of a compiled step's instructions whose
    metadata lies inside one of the scopes above (the innermost)."""
    found = []
    for name, op_name in moe_events.INSTRUCTION.findall(hlo_text):
        scopes = SCOPE.findall(op_name)
        if scopes:
            found.append([name, scopes[-1]])
    return found


def attend_bytes_a_row(config: dict, itemsize: int) -> int:
    """Bytes of one selected token's keys and values over the layers held:
    what the attention reads of it at the least, whatever gathers it."""
    return (2 * config["num_hidden_layers"] * config["num_key_value_heads"]
            * config["head_dim"] * itemsize)


def index_bytes_a_row(config: dict, itemsize: int) -> int:
    """Bytes of one cached token's indexer key over the layers held: what
    the indexer reads of a context row at the least."""
    return (config["num_hidden_layers"]
            * config["sa_config"]["indexer_head_dim"] * itemsize)


def by_scope(run) -> dict:
    """{scope: device seconds} of chip 0's events inside the device's
    pure-decode steps (`other` for those no scope claims), and `steps`,
    those steps: [(Step, {scope: seconds})]."""
    if hasattr(run, "keye2_by_scope"):
        return run.keye2_by_scope
    run.keye2_by_scope = out = {}
    pairs = run.result["counters"].get("keye2_instructions")
    found = device_steps.sound(run)
    steps = [s for s in found.steps if s.kind == "decode"] if found else []
    if not pairs or not steps:
        return out
    scope = dict(map(tuple, pairs))
    ops = sorted((a, b, trace.op_name(text))
                 for text, a, b in run.trace.chips[0].ops)
    starts = [a for a, _, _ in ops]
    out["steps"] = []
    for s in steps:
        mine = {}
        for a, b, name in ops[bisect.bisect_left(starts, s.start):
                              bisect.bisect_right(starts, s.end)]:
            of = scope.get(name) or ("moe.experts"
                                     if moe_events.is_grouped_matmul(name)
                                     else "other")
            mine[of] = mine.get(of, 0.0) + (min(b, s.end) - a) / 1e9
        out["steps"].append((s, mine))
        for of, took in mine.items():
            out[of] = out.get(of, 0.0) + took
    n = len(steps)

    def mean(arg):  # of a count only the engine knows, over the steps
        return sum(s.args.get(arg, 0) for s in steps) / n

    print("[keye2] device ms a pure-decode step by scope: "
          + ", ".join(f"{k} {v / n * 1e3:.3f}"
                      for k, v in sorted(out.items()) if k != "steps")
          + f"; the step's own interval "
          f"{sum(s.ms for s in steps) / n:.3f} ({n} device steps); a "
          f"step's rows attend {mean('sel_rows'):.0f} of the "
          f"{mean('ctx_rows'):.0f} cached rows they score "
          f"({mean('index_rows'):.0f} indexer keys read) a layer")
    return out


def per_step_ms(run, scopes):
    found = by_scope(run)
    took = sum(found.get(s, 0.0) for s in scopes)
    return took / len(found["steps"]) * 1e3 if took else None


def roofline_pct(run, scopes, least_bytes):
    """100 x the seconds the chip needs at the least to read
    `least_bytes(step arguments)` at its HBM bandwidth, over the seconds
    the events under `scopes` took, summed over the pure-decode steps;
    None where a step lacks an argument or nothing ran under the
    scopes."""
    found = by_scope(run)
    try:
        moved = sum(least_bytes(s.args) for s, _ in found.get("steps", []))
    except KeyError:
        return None
    took = sum(found.get(s, 0.0) for s in scopes)
    if not took or not moved:
        return None
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / took
