"""Pass 4 — donation/aliasing checker.

Every hot-loop executable donates its carried state (train step, chunked
scan, decode step's KV caches): on backends that honor donation the
input buffer is DEAD after the call, and a host-side read of it returns
garbage or raises — but only on those backends, so the bug ships green
from a CPU test run. Two checks:

1. **Reuse-after-donation** (lint rule `donated_reuse`): at every call
   site of a known donated executable, a buffer passed at a donated
   argnum must be rebound by the call's own assignment (the carry
   pattern) or never referenced again. Scanned over the runtime modules
   (model.fit's step loop, the pipelined engine's chunk dispatch, the
   serving engine's decode step).

2. **Registry cross-check**: the analysis's own table of donated argnums
   (`lint.DONATED_CALLEES`) is verified against `executor.py`'s AST —
   the `donate_argnums=_donate_argnums((...))` declarations inside each
   `build_*` method. The checker re-derives the donation contract from
   the source instead of trusting its own table, the same
   independent-re-derivation discipline as the sharding pass; if the
   executor grows or changes a donated argnum and the table lags, the
   pass fails loudly instead of silently scanning with stale argnums.
"""

from __future__ import annotations

import ast
import os

from .findings import Finding, SEV_ERROR, SEV_INFO
from .lint import DONATED_CALLEES
from .sources import package_root, runtime_findings

PASS_NAME = "donation_aliasing"

# executor build method → the call-site names its executable binds to
# (the names runtime code assigns the jitted fn to)
BUILDER_CALLEES = {
    "build_train_step": ("step_fn", "_train_step"),
    "build_chunked_train_step": ("chunk_fn",),
    "build_eval_step": ("eval_fn", "_eval_step"),
    "build_decode_step": ("_step_fn", "_decode_step"),
    # speculative decoding's batched multi-token verification: the
    # target's KV state is donated, so the engine rebinds it per call
    "build_verify_step": ("_verify_fn", "_verify_step"),
    "build_block_copy": ("_copy_fn", "_copy_fn_w"),
    # disaggregated serving's KV handoff landing: the decode-side pools
    # are donated, so the coordinator rebinds the decode state
    "build_kv_inject": ("_inject_fn",),
    # stage-3 (ZeRO-3/FSDP) full-gather of the sharded-at-rest param
    # tree: callers must rebind the donated tree (bench/smoke pattern)
    "build_param_gather": ("_gather_fn", "gather_fn"),
}


def executor_donation_table(executor_path: str = "") -> dict:
    """{build method name: donated argnums tuple} extracted from
    executor.py's AST — the ground truth the registry is checked
    against."""
    path = executor_path or os.path.join(package_root(), "executor.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    out: dict[str, tuple] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.FunctionDef) or \
                not node.name.startswith("build_"):
            continue
        for call in ast.walk(node):
            if not isinstance(call, ast.Call):
                continue
            for kw in call.keywords:
                if kw.arg != "donate_argnums":
                    continue
                v = kw.value
                # donate_argnums=_donate_argnums((0, 1, ...)) or a bare
                # tuple literal
                if isinstance(v, ast.Call) and v.args:
                    v = v.args[0]
                if isinstance(v, ast.Tuple):
                    try:
                        nums = tuple(ast.literal_eval(v))
                    except (ValueError, SyntaxError):
                        continue
                    out[node.name] = nums
    return out


_registry_cache: dict = {}


def registry_problems(executor_path: str = "") -> list[Finding]:
    """Cross-check DONATED_CALLEES against the executor source. Cached
    per path for the life of the process (the source cannot change under
    a running compile)."""
    hit = _registry_cache.get(executor_path)
    if hit is not None:
        return list(hit)
    findings = _registry_problems_uncached(executor_path)
    _registry_cache[executor_path] = list(findings)
    return findings


def _registry_problems_uncached(executor_path: str = "") -> list[Finding]:
    findings: list[Finding] = []
    try:
        table = executor_donation_table(executor_path)
    except (OSError, SyntaxError) as e:
        return [Finding(
            SEV_ERROR, "donation_registry_mismatch",
            f"could not read executor donation declarations: {e}",
            pass_name=PASS_NAME)]
    for builder, callees in BUILDER_CALLEES.items():
        actual = table.get(builder)
        if actual is None:
            findings.append(Finding(
                SEV_ERROR, "donation_registry_mismatch",
                f"executor has no donate_argnums declaration for "
                f"{builder}() — registry expects one",
                where=f"executor.py:{builder}"))
            continue
        for callee in callees:
            expected = DONATED_CALLEES.get(callee)
            if expected != actual:
                findings.append(Finding(
                    SEV_ERROR, "donation_registry_mismatch",
                    f"registry says {callee}() donates {expected}, "
                    f"executor.{builder}() declares {actual} — the "
                    f"donated-reuse scan would run with stale argnums",
                    where=f"executor.py:{builder}",
                    details={"registry": list(expected or ()),
                             "executor": list(actual)}))
    for builder in table:
        if builder not in BUILDER_CALLEES:
            findings.append(Finding(
                SEV_ERROR, "donation_registry_mismatch",
                f"executor.{builder}() declares donation but the "
                f"registry has no call-site names for it — its call "
                f"sites are unscanned",
                where=f"executor.py:{builder}"))
    return findings


def run(graph, mesh, ctx=None) -> list[Finding]:
    findings = registry_problems()
    findings.extend(runtime_findings(("donated_reuse",)))
    if not findings:
        findings.append(Finding(
            SEV_INFO, "donation_clean",
            f"{len(BUILDER_CALLEES)} donated executables: registry "
            f"matches executor declarations, no host-side reuse of "
            f"donated buffers"))
    return findings
