"""The device events of the delta-rule layers and of grouped-KV paged
decode in a traced run of `solar2-serve-reason`, and the functions that
count the bytes their rooflines are held to.

The program wraps the parts of a delta-rule layer in `jax.named_scope`s
(`kda.proj`, `kda.conv`, `kda.gate`, `kda.state`, `kda.out`:
ops/delta_attention.py) and runs the state update as the Pallas kernel
`delta_rule_update` (kernels/delta_rule.py); the softmax layer's decode is
the Pallas kernel `flash_attention_paged_decode_grouped`. A TPU trace names
an event by its instruction and holds no scope (benchmarks/moe_events.py),
so a traced run of the job compiles the text of the engine's pure-decode
step once in set-up and leaves `[instruction name, scope]` pairs among its
counters (`solar2_instructions`). The events read are those of the device's
steps that only decode, found from the device's own events
(`device_steps`), and the readers give milliseconds a such step. A run that left no pairs, or a program
without the spans or the scopes (a parent commit), has nothing to read and
the readers return None.
"""

from __future__ import annotations

import re

from benchmarks import moe_events, program_spans, trace

SCOPE = re.compile(r"(kda\.(?:proj|conv|gate|state|out)|gqa\.attend)")
STATE = ("kda.state",)
MIX = ("kda.proj", "kda.conv", "kda.gate", "kda.out")
STATE_KERNEL = "delta_rule_update"
DECODE_KERNEL = "flash_attention_paged_decode"


def scoped_instructions(hlo_text: str) -> list:
    """[[instruction name, scope]] of a compiled step's instructions whose
    metadata lies inside one of the scopes above (the innermost)."""
    found = []
    for name, op_name in moe_events.INSTRUCTION.findall(hlo_text):
        scopes = SCOPE.findall(op_name)
        if scopes:
            found.append([name, scopes[-1]])
    return found


def state_bytes_a_slot(config: dict) -> int:
    """Bytes of one slot's recurrent state over the delta-rule layers held
    (float32, heads x d x d a layer): a decode step reads them once and
    writes them once at the least."""
    lin = config["linear_attn_config"]
    layers = sum(i not in config["gqa_layers"]
                 for i in range(config["num_hidden_layers"]))
    return 4 * layers * lin["num_heads"] * lin["head_dim"] ** 2


def kv_bytes_a_row(config: dict, itemsize: int) -> int:
    """Bytes of one token's keys and values over the softmax layers held:
    what the paged decode kernel reads of a context row at the least."""
    layers = sum(i in config["gqa_layers"]
                 for i in range(config["num_hidden_layers"]))
    return (2 * layers * config["num_key_value_heads"] * config["head_dim"]
            * itemsize)


def layers(config: dict) -> tuple:
    """(softmax layers, delta-rule layers) of the layers held."""
    n = config["num_hidden_layers"]
    softmax = sum(i in config["gqa_layers"] for i in range(n))
    return softmax, n - softmax


def device_steps(run) -> list:
    """Chip 0's events inside the window, cut into the device's own steps:
    a step program runs the paged decode kernel once a softmax layer, the
    first of them first among the scoped work, so a step is what runs from
    one such event (every `softmax layers`-th) to the next; what stands
    before the first and after the last is dropped. [(events, is a step
    that only decodes)]: a step that only decodes runs the state kernel
    once a delta-rule layer, a step with a chunk twice. The host's spans
    are not used: with a step in flight they lie a little after the
    device's work (PERF.md section 7)."""
    softmax, delta = layers(run.config)
    lo, hi = run.trace.window
    ops = sorted(((a, b, trace.op_name(text))
                  for text, a, b in run.trace.chips[0].ops
                  if a >= lo and b <= hi))
    marks = [i for i, (_, _, name) in enumerate(ops)
             if name.startswith(DECODE_KERNEL)][::max(softmax, 1)]
    steps = []
    for first, after in zip(marks, marks[1:]):
        events = ops[first:after]
        updates = sum(name.startswith(STATE_KERNEL) for _, _, name in events)
        steps.append((events, updates == delta))
    return steps


def by_scope(run) -> dict:
    """{scope: device seconds} of chip 0's events in the device steps that
    only decode (the two kernels under their scopes), `steps`, how many
    those are, and `spans`, the `ff/serve.step` spans (for the counts only
    the engine knows)."""
    if hasattr(run, "solar2_by_scope"):
        return run.solar2_by_scope
    pairs = run.result["counters"].get("solar2_instructions")
    spans = program_spans.named(run, "ff/serve.step")
    run.solar2_by_scope = out = {}
    if not pairs or not spans:
        return out
    pure = [events for events, decodes in device_steps(run) if decodes]
    if not pure:
        return out
    scope = dict(map(tuple, pairs))
    for events in pure:
        for a, b, name in events:
            of = scope.get(name)
            if name.startswith(STATE_KERNEL):
                of = "kda.state"
            elif name.startswith(DECODE_KERNEL):
                of = "gqa.attend"
            if of:
                out[of] = out.get(of, 0.0) + (b - a) / 1e9
    print("[solar2] device ms a pure-decode step by scope: "
          + ", ".join(f"{k} {v / len(pure) * 1e3:.3f}"
                      for k, v in sorted(out.items()))
          + f" ({len(pure)} device steps, {len(spans)} spans)")
    out["steps"], out["spans"] = len(pure), spans
    return out


def seconds_a_step(run, scopes):
    """Device seconds a step that only decodes spends under `scopes`, or
    None where nothing was found."""
    found = by_scope(run)
    took = sum(found.get(s, 0.0) for s in scopes)
    return took / found["steps"] if took else None


def per_step_ms(run, scopes):
    took = seconds_a_step(run, scopes)
    return took * 1e3 if took else None


def span_mean(run, value):
    """The mean over the pure-decode iterations' spans of `value(span
    arguments)`, or None where a span lacks what it reads."""
    spans = by_scope(run).get("spans") or []
    try:
        return sum(value(s[3]) for s in spans) / len(spans)
    except (KeyError, ZeroDivisionError):
        return None
