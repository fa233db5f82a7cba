"""The device events of EVA attention in a traced run of
`evabyte-serve-bytedocs`, and the functions that count the rows and bytes
its core's roofline is held to.

The program wraps an EVA layer in `jax.named_scope`s: `eva.qkv` and
`eva.out` (the projections), `eva.attend` (the core over both sets of
keys: the exact rows of the aligned window and the summaries of the closed
windows, however many kernel calls implement it) and `eva.summarise` (the
chunk summaries' computation and write) (ops/attention.AttentionFrontEnd,
ops/inc_attention.py). A TPU trace names an event by its instruction and
holds no scope (benchmarks/moe_events.py), so a traced run of the job
compiles the text of the engine's pure-decode step once in set-up and
leaves `[instruction name, scope]` pairs among its counters
(`evabyte_instructions`). A step's events are those inside the device's
own interval for it (`device_steps.sound(run).steps`, kind `decode`), as
mimo2_events.py takes them; what a step's rows attend is the engine's own
count on the step's span (`eva_exact_rows`, `eva_summary_rows`: rows a
layer). A run that left no pairs, a program without the scopes or the
counters (a parent commit), or a join at fault has nothing to read and the
readers return None.
"""

from __future__ import annotations

import bisect
import re

from benchmarks import device_steps, moe_events, trace

SCOPE = re.compile(r"(eva\.(?:qkv|attend|summarise|out))")
ATTEND = ("eva.attend",)
SUMMARISE = ("eva.summarise",)
PROJECTIONS = ("eva.qkv", "eva.out")


def scoped_instructions(hlo_text: str) -> list:
    """[[instruction name, scope]] of a compiled step's instructions whose
    metadata lies inside one of the scopes above (the innermost)."""
    found = []
    for name, op_name in moe_events.INSTRUCTION.findall(hlo_text):
        scopes = SCOPE.findall(op_name)
        if scopes:
            found.append([name, scopes[-1]])
    return found


def rows_attended(position: int, config: dict) -> tuple:
    """(exact rows, summary rows) a row at `position` attends in one layer:
    its aligned window from its start to itself, and one summary for every
    chunk of the windows closed before it."""
    window, chunk = config["window_size"], config["chunk_size"]
    return (position % window + 1, position // window * (window // chunk))


def step_rows(positions, config: dict) -> tuple:
    """(exact rows, summary rows) the rows at `positions` attend, a
    layer."""
    rows = [rows_attended(t, config) for t in positions]
    return sum(r[0] for r in rows), sum(r[1] for r in rows)


def row_bytes(config: dict, itemsize: int) -> int:
    """Bytes of one attended row, exact or summary, over the layers held:
    a key and a value of every KV head (8 x 2 x 32 x 128 x 2 B = 131,072 B
    in bf16, 16,384 B a layer)."""
    head = config["hidden_size"] // config["num_attention_heads"]
    return (config["num_hidden_layers"] * 2 * config["num_key_value_heads"]
            * head * itemsize)


def by_scope(run) -> dict:
    """{scope: device seconds} of chip 0's events inside the device's
    pure-decode steps under the scopes above, `other` for the rest, and
    `steps`, those steps."""
    if hasattr(run, "evabyte_by_scope"):
        return run.evabyte_by_scope
    run.evabyte_by_scope = out = {}
    pairs = run.result["counters"].get("evabyte_instructions")
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
            of = scope.get(name, "other")
            out[of] = out.get(of, 0.0) + (min(b, s.end) - a) / 1e9
    n = len(steps)

    def mean(arg):  # of a count only the engine knows, over the steps
        return sum(s.args.get(arg, 0) for s in steps) / n

    print("[evabyte] device ms a pure-decode step by scope: "
          + ", ".join(f"{k} {v / n * 1e3:.3f}"
                      for k, v in sorted(out.items()) if k != "steps")
          + f"; the step's own interval {sum(s.ms for s in steps) / n:.3f} "
          f"({n} device steps); a step's rows attend, a layer, "
          f"{mean('eva_exact_rows'):.0f} exact rows and "
          f"{mean('eva_summary_rows'):.0f} summaries, write "
          f"{mean('eva_summaries_written'):.2f} summaries, and "
          f"{mean('eva_rollovers'):.3f} slots open a window")
    return out


def per_step_ms(run, scopes):
    found = by_scope(run)
    took = sum(found.get(s, 0.0) for s in scopes)
    return took / len(found["steps"]) * 1e3 if took else None


def attend_roofline_pct(run):
    """100 x the seconds the chip needs at the least to read the rows the
    pure-decode steps' rows attend (exact and summary, `row_bytes` each,
    at the item size the cache is stored in) at its HBM bandwidth, over the
    seconds the events under `eva.attend` took; None where a step lacks a
    count or nothing ran under the scope. Counted from the span's rows,
    whatever implements the core: a kernel that reads whole pages reads
    more, which shows as a lower share."""
    found = by_scope(run)
    try:
        moved = sum((s.args["eva_exact_rows"] + s.args["eva_summary_rows"])
                    * row_bytes(run.config, s.args["kv_itemsize"])
                    for s in found.get("steps", []))
    except KeyError:
        return None
    took = found.get("eva.attend", 0.0)
    if not took or not moved:
        return None
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / took


def summary_rows_pct(run):
    """The share of the rows the window's decoding rows attended that are
    summaries, from the engine's totals (`engine.stats()` when the window
    closed less when it opened)."""
    c = run.result["counters"]
    exact, summary = c.get("eva_exact_rows"), c.get("eva_summary_rows")
    if not exact or summary is None:
        return None
    return 100.0 * summary / (exact + summary)
