"""The device events of the attention projections and of the shared experts
in a traced run of `cmdap-serve-agentmix`, and the function that counts the
bytes the shared experts' roofline is held to.

The program wraps an attention layer's projections in `jax.named_scope`s
of the layer's kind (`gqa.qkv` / `gqa.out` where it attends its whole
past, `swa.qkv` / `swa.out` where a window: ops/attention.
AttentionFrontEnd.scope) and the shared experts in `moe.shared`
(ops/moe.py). A TPU trace names an event by its instruction and holds no
scope (benchmarks/moe_events.py), so a traced run of the job compiles the
text of the engine's pure-decode step once in set-up and leaves
`[instruction name, scope]` pairs among its counters
(`cmdap_instructions`). A step's events are those inside the device's own
interval for it (`device_steps.sound(run).steps`, kind `decode`), as
mimo2_events.py takes them. A run that left no pairs, a program without
the scopes (a parent commit), or a join at fault has nothing to read and
the readers return None.
"""

from __future__ import annotations

import bisect
import re

from benchmarks import device_steps, moe_events, trace

SCOPE = re.compile(r"((?:gqa|swa)\.(?:qkv|out)|moe\.shared)")
PROJECTIONS = ("gqa.qkv", "gqa.out", "swa.qkv", "swa.out")
SHARED = ("moe.shared",)


def scoped_instructions(hlo_text: str) -> list:
    """[[instruction name, scope]] of a compiled step's instructions whose
    metadata lies inside one of the scopes above (the innermost)."""
    found = []
    for name, op_name in moe_events.INSTRUCTION.findall(hlo_text):
        scopes = SCOPE.findall(op_name)
        if scopes:
            found.append([name, scopes[-1]])
    return found


def shared_expert_bytes(config: dict, itemsize: int) -> int:
    """Bytes of the shared experts' weights over the layers held: what a
    step reads of them at the least, once (4 layers x 4 experts x 3
    matrices of 4,096 x 4,096 = 4 x 201.33 M numbers)."""
    return (config["num_hidden_layers"] * config["num_shared_experts"] * 3
            * config["hidden_size"] * config["intermediate_size"] * itemsize)


def by_scope(run) -> dict:
    """{scope: device seconds} of chip 0's events inside the device's
    pure-decode steps under the scopes above, and `steps`, the number of
    those steps."""
    if hasattr(run, "cmdap_by_scope"):
        return run.cmdap_by_scope
    run.cmdap_by_scope = out = {}
    pairs = run.result["counters"].get("cmdap_instructions")
    found = device_steps.sound(run)
    steps = [s for s in found.steps if s.kind == "decode"] if found else []
    if not pairs or not steps:
        return out
    scope = dict(map(tuple, pairs))
    ops = sorted((a, b, trace.op_name(text))
                 for text, a, b in run.trace.chips[0].ops)
    starts = [a for a, _, _ in ops]
    out["steps"] = len(steps)
    for s in steps:
        for a, b, name in ops[bisect.bisect_left(starts, s.start):
                              bisect.bisect_right(starts, s.end)]:
            of = scope.get(name)
            if of:
                out[of] = out.get(of, 0.0) + (min(b, s.end) - a) / 1e9
    print("[cmdap] device ms a pure-decode step by scope: "
          + ", ".join(f"{k} {v / len(steps) * 1e3:.3f}"
                      for k, v in sorted(out.items()) if k != "steps")
          + f" ({len(steps)} device steps)")
    return out


def per_step_ms(run, scopes):
    found = by_scope(run)
    took = sum(found.get(s, 0.0) for s in scopes)
    return took / found["steps"] * 1e3 if took else None


def shared_roofline_pct(run):
    """100 x the seconds the chip needs at the least to read the shared
    experts' weights once a pure-decode step at its HBM bandwidth, over
    the seconds the events under `moe.shared` took; None where nothing ran
    under the scope or the run left no item size."""
    found = by_scope(run)
    took = found.get("moe.shared", 0.0)
    itemsize = run.result["counters"].get("weight_itemsize")
    if not took or not itemsize:
        return None
    moved = found["steps"] * shared_expert_bytes(run.config, itemsize)
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / took
