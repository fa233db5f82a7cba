"""The device events of the global and the window attention layers in a
traced run of `mimo2f-serve-longdoc`, and the functions that count the
bytes their rooflines are held to.

The program wraps a layer's core in a `jax.named_scope`: `swa.attend` where
the layer attends a window, `gqa.attend` where it attends its whole past
(ops/attention.AttentionFrontEnd.attend_scope, ops/inc_attention.py). A TPU
trace names an event by its instruction and holds no scope
(benchmarks/moe_events.py), so a traced run of the job compiles the text of
the engine's pure-decode step once in set-up and leaves `[instruction name,
scope]` pairs among its counters (`mimo2_instructions`). A step's events
are those inside the device's own interval for it
(`device_steps.sound(run).steps`, kind `decode`); what only the engine
knows of a step (`kv_rows`: the context rows its slots hold, which a global
layer reads; `window_rows`: the rows a window layer reads of them, a slot's
last 128 or its context where that is shorter; `kv_itemsize`) comes from
the step's own `ff/serve.step` arguments, joined by its id. A run that left
no pairs, a program without the scopes or the span's `window_rows` (a
parent commit), or a join at fault has nothing to read and the readers
return None.
"""

from __future__ import annotations

import bisect
import re

from benchmarks import device_steps, moe_events, trace

SCOPE = re.compile(r"(swa\.attend|gqa\.attend"
                   r"|moe\.(?:route|dispatch|experts|combine|shared))")
FULL = ("gqa.attend",)
WINDOW = ("swa.attend",)


def scoped_instructions(hlo_text: str) -> list:
    """[[instruction name, scope]] of a compiled step's instructions whose
    metadata lies inside one of the scopes above (the innermost)."""
    found = []
    for name, op_name in moe_events.INSTRUCTION.findall(hlo_text):
        scopes = SCOPE.findall(op_name)
        if scopes:
            found.append([name, scopes[-1]])
    return found


def layers_of(config: dict, window: bool) -> int:
    pattern = config["hybrid_layer_pattern"][:config["num_hidden_layers"]]
    return sum(bool(kind) == window for kind in pattern)


def bytes_a_row(config: dict, window: bool, itemsize: int) -> int:
    """Bytes of one cached token's keys and values over the layers of one
    kind that are held: what their attention reads of a row it attends at
    the least, whatever reads it (2 x 2,560 B in the global layers, 5 x
    5,120 B in the window layers, in bf16)."""
    kind = "swa_" if window else ""
    return (layers_of(config, window) * config[kind + "num_key_value_heads"]
            * (config[kind + "head_dim"] + config[kind + "v_head_dim"])
            * itemsize)


def by_scope(run) -> dict:
    """{scope: device seconds} of chip 0's events inside the device's
    pure-decode steps (`other` for those no scope claims), and `steps`,
    those steps."""
    if hasattr(run, "mimo2_by_scope"):
        return run.mimo2_by_scope
    run.mimo2_by_scope = out = {}
    pairs = run.result["counters"].get("mimo2_instructions")
    found = device_steps.sound(run)
    steps = [s for s in found.steps if s.kind == "decode"] if found else []
    if not pairs or not steps:
        return out
    scope = dict(map(tuple, pairs))
    ops = sorted((a, b, trace.op_name(text))
                 for text, a, b in run.trace.chips[0].ops)
    starts = [a for a, _, _ in ops]
    out["steps"] = steps
    for s in steps:
        for a, b, name in ops[bisect.bisect_left(starts, s.start):
                              bisect.bisect_right(starts, s.end)]:
            of = scope.get(name) or ("moe.experts"
                                     if moe_events.is_grouped_matmul(name)
                                     else "other")
            out[of] = out.get(of, 0.0) + (min(b, s.end) - a) / 1e9
    n = len(steps)

    def mean(arg):  # of a count only the engine knows, over the steps
        return sum(s.args.get(arg, 0) for s in steps) / n

    print("[mimo2] device ms a pure-decode step by scope: "
          + ", ".join(f"{k} {v / n * 1e3:.3f}"
                      for k, v in sorted(out.items()) if k != "steps")
          + f"; the step's own interval "
          f"{sum(s.ms for s in steps) / n:.3f} ({n} device steps); a "
          f"step's slots hold {mean('kv_rows'):.0f} context rows, of which "
          f"a window layer reads {mean('window_rows'):.0f}")
    return out


def per_step_ms(run, scopes):
    found = by_scope(run)
    took = sum(found.get(s, 0.0) for s in scopes)
    return took / len(found["steps"]) * 1e3 if took else None


def roofline_pct(run, scopes, rows_arg: str, window: bool):
    """100 x the seconds the chip needs at the least to read the step's
    `rows_arg` rows of keys and values in the layers of one kind at its HBM
    bandwidth, over the seconds the events under `scopes` took, summed
    over the pure-decode steps; None where a step lacks an argument or
    nothing ran under the scopes."""
    found = by_scope(run)
    try:
        moved = sum(s.args[rows_arg] * bytes_a_row(
            run.config, window, s.args["kv_itemsize"])
            for s in found.get("steps", []))
    except KeyError:
        return None
    took = sum(found.get(s, 0.0) for s in scopes)
    if not took or not moved:
        return None
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / took
