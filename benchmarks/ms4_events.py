"""The device events of latent attention with no selection and of the
expert layer in a traced run of `ms4-serve-longctx`, and the functions that
count the bytes and the FLOPs the paged latent kernel is held to.

The program wraps the parts of a layer in `jax.named_scope`s (`mla.q`,
`mla.kv`, `mla.attend`, `mla.out`: ops/latent_attention.py; `moe.route`,
`moe.dispatch`, `moe.experts`, `moe.combine`, `moe.shared`: ops/moe.py),
and the kernel that reads a decoding row's whole latent history is a
Pallas call of its own name, `paged_latent_decode`
(kernels/paged_latent_attention.py), which a TPU trace shows as
`paged_latent_decode.<n>`. A TPU trace names every other event by its
instruction and holds no scope (benchmarks/moe_events.py), so a traced run
of the job compiles the text of the engine's pure-decode step once in
set-up and leaves `[instruction name, scope]` pairs among its counters
(`ms4_instructions`). A step's events are those inside the device's own
interval for it (`device_steps.sound(run).steps`, kind `decode`: first to
last operation of the step's execution), not inside the host's span, which
with a step in flight lies a little after the device's work (PERF.md
section 7, "From PR 32"); what only the engine knows of a step (`kv_rows`:
the context rows its slots hold, every one of which the attention reads;
`kv_itemsize`) comes from the step's own `ff/serve.step` arguments, joined
by its id. A run that left no pairs, a program without the kernel or the
scopes (a parent commit), or a join at fault has nothing to read and the
readers return None.
"""

from __future__ import annotations

import bisect
import re

from benchmarks import device_steps, moe_events, trace

SCOPE = re.compile(r"(mla\.(?:q|kv|attend|out)"
                   r"|moe\.(?:route|dispatch|experts|combine|shared))")
ATTEND = ("mla.attend",)
KERNEL = "paged_latent_decode"


def scoped_instructions(hlo_text: str) -> list:
    """[[instruction name, scope]] of a compiled step's instructions whose
    metadata lies inside one of the scopes above (the innermost)."""
    found = []
    for name, op_name in moe_events.INSTRUCTION.findall(hlo_text):
        scopes = SCOPE.findall(op_name)
        if scopes:
            found.append([name, scopes[-1]])
    return found


def latent_bytes_a_row(config: dict, itemsize: int) -> int:
    """Bytes of one cached token's published latent row [c_kv ; k_R] over
    the layers held: what the attention reads of a row at the least,
    whatever the pool stores (640 B a layer in bf16, 3,840 B over six)."""
    return (config["num_hidden_layers"]
            * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            * itemsize)


def kernel_flops_a_row(config: dict) -> int:
    """FLOPs the kernel spends on one cached row of one slot over the
    layers held: every head's score over the published row and its
    weighted sum over the latent (2 x 32 x (320 + 256) = 36,864 a layer;
    the stored row's zero lanes are multiplied too and not counted)."""
    return (2 * config["num_hidden_layers"] * config["num_attention_heads"]
            * (2 * config["kv_lora_rank"] + config["qk_rope_head_dim"]))


def by_scope(run) -> dict:
    """{scope: device seconds} of chip 0's events inside the device's
    pure-decode steps (`other` for those no scope claims; `kernel`: the
    events named paged_latent_decode*, which lie under `mla.attend` too),
    and `steps`, those steps."""
    if hasattr(run, "ms4_by_scope"):
        return run.ms4_by_scope
    run.ms4_by_scope = out = {}
    pairs = run.result["counters"].get("ms4_instructions")
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
            took = (min(b, s.end) - a) / 1e9
            of = scope.get(name) or ("moe.experts"
                                     if moe_events.is_grouped_matmul(name)
                                     else "other")
            if name.startswith(KERNEL):
                out["kernel"] = out.get("kernel", 0.0) + took
                of = ATTEND[0]
            out[of] = out.get(of, 0.0) + took
    n = len(steps)
    rows = sum(s.args.get("kv_rows", 0) for s in steps)
    kernel = out.get("kernel", 0.0)
    said = ""
    if kernel and rows:
        rate = rows * kernel_flops_a_row(run.config) / kernel
        said = (f"; the kernel reads {rows / n:.0f} latent rows a step a "
                f"layer at {rate / 1e12:.2f} TFLOP/s, "
                f"{100 * rate / run.peaks['bf16_flops_per_s']:.1f} % of the "
                f"MXU's peak")
    print("[ms4] device ms a pure-decode step by scope: "
          + ", ".join(f"{k} {v / n * 1e3:.3f}"
                      for k, v in sorted(out.items()) if k != "steps")
          + f"; the step's own interval "
          f"{sum(s.ms for s in steps) / n:.3f} ({n} device steps)" + said)
    return out


def per_step_ms(run, scopes):
    found = by_scope(run)
    took = sum(found.get(s, 0.0) for s in scopes)
    return took / len(found["steps"]) * 1e3 if took else None


def kernel_roofline_pct(run):
    """100 x the seconds the chip needs at the least to read the steps'
    `kv_rows` published latent rows at its HBM bandwidth, over the seconds
    the kernel's events took, summed over the pure-decode steps; None
    where a step lacks an argument or no such kernel ran."""
    found = by_scope(run)
    try:
        moved = sum(s.args["kv_rows"] * latent_bytes_a_row(
            run.config, s.args["kv_itemsize"])
            for s in found.get("steps", []))
    except KeyError:
        return None
    took = found.get("kernel", 0.0)
    if not took or not moved:
        return None
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / took
