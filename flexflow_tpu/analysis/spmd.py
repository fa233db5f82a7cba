"""Pass 6 — cross-host SPMD divergence detector (the SPMD half of ffsan).

Multi-controller JAX is correct only while every process traces and
dispatches the SAME program against the SAME plan. The repo has already
paid for two instances of the divergence class: r13's per-host pricing
divergence (calibration measured different costs per host, the
update-sharding auto verdict flipped on one of them — fixed by
`broadcast_json`-ing the coordinator's decision), and the
`coordinator_collective` deadlock idiom ffcheck pass 3 lints for. This
pass generalizes both:

1. **Static**: the `host_divergent_branch` lint rule (analysis/lint.py)
   over the runtime modules — an `if` whose test calls a per-host-
   nondeterministic source (time, RNG, environment, hostname) guarding a
   collective (deadlock: some hosts never arrive) or a trace-entry call
   (divergent executables: hosts compile different programs).
2. **Runtime** (opt-in, `--spmd-barrier`): `fingerprint_barrier` —
   before the first step, every process hashes the ingredients of its
   step executable (plan fingerprint + strategy, donation registry and
   whether the backend donates, update-spec layout, mesh axes,
   numerics policy) and compares against the coordinator's over the
   `broadcast_json` channel. A mismatch raises `SPMDDivergenceError` on
   every process in lockstep — a structured abort at t=0 instead of a
   silent hang or corrupted training hours later. Costs one small
   broadcast; zero when off.
"""

from __future__ import annotations

import hashlib
import json

from .findings import Finding, SEV_INFO
from .sources import runtime_findings

PASS_NAME = "spmd_uniformity"


class SPMDDivergenceError(RuntimeError):
    """Raised by the fingerprint barrier when the fleet's step
    fingerprints disagree. Carries both payloads so the first diverging
    component is printable; `peer_mismatch` marks the processes whose
    OWN fingerprint matched the coordinator's but which must still
    abort because a peer diverged (the lockstep half of the barrier)."""

    def __init__(self, local: dict, remote: dict,
                 peer_mismatch: bool = False):
        self.local = local
        self.remote = remote
        self.peer_mismatch = peer_mismatch
        if peer_mismatch:
            msg = ("SPMD fingerprint mismatch before the first step — "
                   "this process matches the coordinator, but a peer "
                   "process reported a divergent step fingerprint; "
                   "aborting in lockstep with it.")
        else:
            diverged = sorted(
                k for k in set(local) | set(remote)
                if local.get(k) != remote.get(k))
            msg = (
                "SPMD fingerprint mismatch before the first step — "
                "this process would run a different program than the "
                f"coordinator. Diverging component(s): {diverged}. "
                "Typical causes: per-host control flow on time/RNG/env "
                "(fflint host_divergent_branch), a plan adopted on one "
                "host only, or backends that differ in whether they "
                "donate.")
        super().__init__(msg)


def run(graph, mesh, ctx=None) -> list[Finding]:
    """Static half: host-divergent branches in the runtime host code.
    (Source scan is cached per process alongside the pass-3/4 rules —
    sources._scan — so the compile gate parses each module once.)"""
    findings = list(runtime_findings(("host_divergent_branch",)))
    if not findings:
        findings.append(Finding(
            SEV_INFO, "spmd_clean",
            "no host-divergent branches feeding collectives or traced "
            "code in the runtime modules"))
    return findings


# --------------------------------------------------------------- runtime


def fingerprint_payload(model) -> dict:
    """The per-process ingredients of the step executable, as a dict of
    stable digests. Everything here must be identical across processes
    for the fleet's SPMD programs to stay in lockstep; anything
    legitimately process-local (process_index, local device ids) must
    stay OUT."""
    from ..executor import _donation_supported
    from ..parallel.strategies import Strategy
    from .lint import DONATED_CALLEES

    def digest(obj) -> str:
        return hashlib.sha256(
            json.dumps(obj, sort_keys=True, default=str).encode()
        ).hexdigest()[:16]

    executor = model.executor
    update_specs = dict(executor.update_specs) if executor else {}
    cfg = model.config
    return {
        "graph": f"{model.graph.hash():016x}",
        "plan_fingerprint": str(model._plan_fingerprint),
        "strategy": digest(Strategy(model._strategy or {}).to_json()),
        "mesh_axes": digest({k: int(v)
                             for k, v in dict(model.mesh.shape).items()}),
        # the donation registry AND whether this process's backend
        # donates: hosts that disagree compile different executables
        "donation": digest({
            "registry": {k: list(v) for k, v in DONATED_CALLEES.items()},
            "supported": _donation_supported()}),
        "update_specs": digest(sorted(
            (f"{n}/{w}", str(spec), list(shape))
            for (n, w), (spec, shape) in update_specs.items())),
        "numerics": digest({
            "computation_dtype": str(cfg.computation_dtype),
            "allow_tensor_op_math": bool(
                cfg.allow_tensor_op_math_conversion),
            "sanitize_numerics": bool(
                getattr(cfg, "sanitize_numerics", False)),
            "loss_type": str(model.loss_type),
            "opt_slots": (model.optimizer.num_slots
                          if model.optimizer is not None else 0)}),
    }


def step_fingerprint(model) -> str:
    """One digest over the full payload (the value logged/recorded)."""
    return hashlib.sha256(
        json.dumps(fingerprint_payload(model), sort_keys=True).encode()
    ).hexdigest()[:16]


def _gather_match_flags(match: bool) -> list:
    """All processes' match flags (default channel): a process_allgather
    so EVERY process learns whether ANY peer diverged — the raise must
    be fleet-wide, or the surviving processes deadlock in the next
    collective waiting for the one that aborted."""
    import jax

    if jax.process_count() <= 1:
        return [bool(match)]
    import numpy as np
    from jax.experimental import multihost_utils

    flags = multihost_utils.process_allgather(
        np.asarray([bool(match)]))
    return [bool(f) for f in np.asarray(flags).reshape(-1)]


def fingerprint_barrier(model, broadcast=None, gather=None) -> dict:
    """Cross-host uniformity barrier, two phases: (1) the coordinator
    broadcasts its fingerprint payload and every process compares;
    (2) the per-process match flags are allgathered so a mismatch
    raises SPMDDivergenceError on EVERY process in lockstep — including
    the coordinator and matching peers, who would otherwise proceed
    into the first collective and hang waiting for the aborted process.
    Returns the verdict record ({status, fingerprint}) that
    strategy_report.json and the compile metrics record carry.

    `broadcast` / `gather` default to the real multihost channels and
    are injectable so a divergence can be simulated single-process
    (tests, ffcheck self-test). Single-process runs with the default
    channels short-circuit to status "single_process"."""
    import jax

    from ..distributed import broadcast_json, is_coordinator

    payload = fingerprint_payload(model)
    fp = step_fingerprint(model)
    if broadcast is None and gather is None \
            and jax.process_count() <= 1:
        return {"status": "single_process", "fingerprint": fp}
    broadcast = broadcast or broadcast_json
    remote = broadcast(
        {"payload": payload, "fingerprint": fp}
        if is_coordinator() else None)
    match = remote.get("fingerprint") == fp
    flags = (gather or _gather_match_flags)(match)
    if not all(flags):
        if not match:
            raise SPMDDivergenceError(payload,
                                      remote.get("payload") or {})
        raise SPMDDivergenceError(payload, payload, peer_mismatch=True)
    return {"status": "ok", "fingerprint": fp}
