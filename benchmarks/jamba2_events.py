"""The device events of the state-space layers and of multi-query paged
decode in a traced run of `jamba2-serve-shortchat`, and the functions that
count the bytes their rooflines are held to.

The program wraps the parts of a state-space layer in `jax.named_scope`s
(`ssm.proj`, `ssm.conv`, `ssm.param`, `ssm.state`, `ssm.out`: ops/ssm.py)
and runs the state update as the Pallas kernel `selective_scan_update`
(kernels/selective_scan.py); the two softmax layers' decode is the Pallas
kernel `flash_attention_paged_decode_grouped`. A TPU trace names an event
by its instruction and holds no scope (benchmarks/moe_events.py), so a
traced run of the job takes the text of every step program it compiled
ahead (the step that only decodes and one a chunk bucket) and leaves
`[bucket, instruction name, scope]` triples among its counters
(`jamba2_instructions`; bucket 0 is the step that only decodes). A step's
events are those inside the device's own interval for it
(`device_steps.sound(run).steps`), named by the triples of its own
program. The readers take EVERY step, with a chunk or without: nearly
every step of this cell carries one, and those steps set `serve_tok_s`. A
step with a chunk runs the state kernel twice a state-space layer, the
slots' rows and then the chunk's tokens from its slot's state (the second
reads the first's state: ops/recurrent.decode_rows), so the chunk's calls
are every second event of the kernel's name: they are `ssm.scan` here, and
the slots' calls stay under `ssm.state`. A chunk step whose count of calls
is not twice the layers' is left out. The bytes of h a step's decoding
rows read and write in one layer is the engine's own count on the step's
span (`ssm_state_bytes`, from the op's `step_counts`). A run that left no
triples, a program without the scopes or the spans (a parent commit), or a
join at fault has nothing to read and the readers return None.
"""

from __future__ import annotations

import bisect
import re

from benchmarks import device_steps, moe_events, trace

SCOPE = re.compile(r"(ssm\.(?:proj|conv|param|state|out))")
STATE = ("ssm.state",)
MIX = ("ssm.proj", "ssm.conv", "ssm.param", "ssm.out")
ATTEND = ("mqa.attend",)
STATE_KERNEL = "selective_scan_update"
DECODE_KERNEL = "flash_attention_paged_decode"


def scoped_instructions(hlo_text: str, bucket: int = 0) -> list:
    """[[bucket, instruction name, scope]] of a compiled step's
    instructions whose metadata lies inside one of the scopes above (the
    innermost); `bucket`: the chunk bucket of the step, 0 for the step
    that only decodes."""
    found = []
    for name, op_name in moe_events.INSTRUCTION.findall(hlo_text):
        scopes = SCOPE.findall(op_name)
        if scopes:
            found.append([bucket, name, scopes[-1]])
    return found


def layers(config: dict) -> tuple:
    """(softmax layers, state-space layers) of the layers held."""
    n = config["num_hidden_layers"]
    softmax = sum(i % config["attn_layer_period"]
                  == config["attn_layer_offset"] for i in range(n))
    return softmax, n - softmax


def state_bytes_a_slot(config: dict) -> int:
    """Bytes of one slot's recurrent state h over the state-space layers
    (float32, mamba_expand x hidden_size channels x mamba_d_state a
    layer): a decode step reads them once and writes them once at the
    least."""
    return (4 * layers(config)[1] * config["mamba_expand"]
            * config["hidden_size"] * config["mamba_d_state"])


def kv_bytes_a_row(config: dict, itemsize: int) -> int:
    """Bytes of one token's keys and values over the softmax layers: what
    the paged decode kernel reads of a context row at the least."""
    head = config["hidden_size"] // config["num_attention_heads"]
    return (2 * layers(config)[0] * config["num_key_value_heads"] * head
            * itemsize)


def _events(run, steps):
    """[(step, [(start, end, instruction name)] of chip 0 inside it)]."""
    ops = sorted((a, b, trace.op_name(text))
                 for text, a, b in run.trace.chips[0].ops)
    starts = [a for a, _, _ in ops]
    return [(s, ops[bisect.bisect_left(starts, s.start):
                    bisect.bisect_right(starts, s.end)]) for s in steps]


def by_scope(run) -> dict:
    """{scope: device seconds} of chip 0's events inside the device's own
    steps, with a chunk or without (the slots' calls of the state kernel
    under `ssm.state`, the chunk's under `ssm.scan`, the paged decode
    kernel under `mqa.attend`, whatever the triples say; `other` for the
    rest), `steps`, the steps read, and `decode`, the same sums over the
    steps that only decode."""
    if hasattr(run, "jamba2_by_scope"):
        return run.jamba2_by_scope
    run.jamba2_by_scope = out = {}
    scope_of = {}
    for bucket, name, scope in run.result["counters"].get(
            "jamba2_instructions") or ():
        scope_of.setdefault(bucket, {})[name] = scope
    found = device_steps.sound(run)
    steps = [s for s in found.steps if s.bucket in scope_of] if found else []
    twice = 2 * layers(run.config)[1]
    read, decode = [], {}
    for s, events in _events(run, steps):
        calls = [e for e in events if e[2].startswith(STATE_KERNEL)]
        if s.kind == "chunk" and len(calls) != twice:
            continue
        scans = set(calls[1::2]) if s.kind == "chunk" else ()
        read.append(s)
        for e in events:
            a, b, name = e
            of = scope_of[s.bucket].get(name, "other")
            if e in scans:
                of = "ssm.scan"
            elif name.startswith(STATE_KERNEL):
                of = "ssm.state"
            elif name.startswith(DECODE_KERNEL):
                of = "mqa.attend"
            took = (min(b, s.end) - a) / 1e9
            out[of] = out.get(of, 0.0) + took
            if s.kind == "decode":
                decode[of] = decode.get(of, 0.0) + took
    if not read:
        return out
    out["steps"], out["decode"] = read, decode

    def said(sums, of):
        whole = sum(s.ms for s in of) / 1e3
        ssm = sum(v for k, v in sums.items() if k.startswith("ssm."))
        return (", ".join(f"{k} {v / len(of) * 1e3:.3f}"
                          for k, v in sorted(sums.items()))
                + f"; the step's own interval {whole / len(of) * 1e3:.3f}, "
                f"the state-space layers {100 * ssm / whole:.1f} % of it "
                f"({len(of)} device steps)")

    sums = {k: v for k, v in out.items() if k not in ("steps", "decode")}
    moved = sum(s.args.get("ssm_state_bytes", 0) for s in read) / len(read)
    print(f"[jamba2] device ms a step by scope, every step "
          f"({sum(s.kind == 'chunk' for s in read)} with a chunk, mean "
          f"bucket {sum(s.bucket for s in read) / len(read):.0f}): "
          + said(sums, read) + "; a step's decoding rows read and write "
          f"{moved / 1e6:.1f} MB of h a layer")
    only = [s for s in read if s.kind == "decode"]
    if only:
        print("[jamba2] a step that only decodes: " + said(decode, only))
    return out


def per_step_ms(run, scopes):
    """Device milliseconds a step spends under `scopes`, over every step
    read, or None where nothing was found."""
    found = by_scope(run)
    took = sum(found.get(s, 0.0) for s in scopes)
    return took / len(found["steps"]) * 1e3 if took else None


def state_roofline_pct(run):
    """100 x the seconds the chip needs at the least to read and write the
    h of the steps' decoding rows (the spans' `ssm_state_bytes`, one
    layer's, x the state-space layers) at its HBM bandwidth, over the
    seconds the events under `ssm.state` took (the slots' calls of the
    kernel and what feeds both calls); None where a step lacks the count
    or nothing ran under the scope. The one-token operands and outputs are
    left out, and the kernel also streams the slots that stand idle: the
    share is a floor."""
    found = by_scope(run)
    took = found.get("ssm.state", 0.0)
    try:
        moved = layers(run.config)[1] * sum(
            s.args["ssm_state_bytes"] for s in found.get("steps", []))
    except KeyError:
        return None
    if not took or not moved:
        return None
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / took


def chunk_scan_ms(run):
    """Device milliseconds a step that carries a chunk spends in the
    chunk's scans (`ssm.scan`: module docstring), or None where no such
    step was found."""
    found = by_scope(run)
    chunks = sum(s.kind == "chunk" for s in found.get("steps", []))
    took = found.get("ssm.scan", 0.0)
    return took / chunks * 1e3 if took else None
