"""In-process live-state migration between two compiled plans.

The apply half of fftrans (analysis/transition.py): `migrate_state(old,
new)` moves a compiled FFModel's FULL training state — params, fp32
masters, optimizer slots, step/counters, RNG, serving KV state — onto a
second compiled model of the same logical PCG whose Strategy, mesh
factorization, and/or ZeRO update stage differ, WITHOUT a
checkpoint-restart round trip (Gemini, SOSP '23: recovery time, not
checkpoint time, bounds effective goodput — the same argument applies to
re-planning). The transition is first built and VERIFIED statically
(gate_transition — state-mapping completeness, dtype/shape preservation,
gather paths, transition-time memory, ring bijectivity, schedule
uniformity); only a verified plan touches live state, and
--no-verify-plan downgrades to warnings exactly like the compile gate.

Each transfer is one `jax.device_put` of the live (possibly sharded)
array onto the destination leaf's NamedSharding — XLA owns lowering that
to the gather/slice program the TransitionPlan derived statically; a
put the backend cannot express cross-mesh falls back to the host hop
the plan priced. Values are moved bit-exactly (dtype changes are
verification ERRORS, never silent casts), so a migrated run's
trajectory is bit-identical to a checkpoint-restart of the same state —
the acceptance property tests/test_transition.py and
scripts/migrate_smoke.py pin.

The executed plan (with measured seconds next to the prediction — the
fidelity datapoint the future re-planner's pay-off rule needs) lands on
`new._transition`, and strategy_report.json gains a `transition` section
whose predicted_s reproduces from the JSON alone
(transition.verify_transition_total)."""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.tree_util as jtu
import numpy as np


def _move_leaf(arr, template_leaf):
    """Move one live array onto the destination leaf's placement.
    In-process first (device_put reshards on-device); host hop as the
    fallback when the backend refuses the cross-mesh put. No dtype
    casts — the verifier already guaranteed dtype equality."""
    sharding = getattr(template_leaf, "sharding", None)
    if sharding is None:
        return jax.numpy.asarray(arr)
    try:
        return jax.device_put(arr, sharding)
    except (ValueError, TypeError):
        # one-off fallback per leaf, not a hot loop
        host = np.asarray(jax.device_get(arr))  # fflint: ok host_sync_in_loop
        return jax.device_put(host, sharding)


def migrate_state(old, new, *, plan=None, donate: bool = False) -> dict:
    """Migrate `old`'s live training state onto `new` in-process.

    Both models must be compiled over the same logical PCG (same layer
    names/shapes); Strategy, mesh factorization, and update stage may
    all differ. Builds + verifies the TransitionPlan (raises
    PlanVerificationError naming the leaf and finding class on an
    unverifiable mapping unless --no-verify-plan), executes it, and
    returns the plan JSON with `measured_s` filled in. `donate=True`
    additionally deletes each source buffer once its transfer lands —
    the donation schedule the transition_memory pass accounts for.
    """
    from .. import telemetry

    assert getattr(old, "_compiled", False), "compile() old before migrating"
    assert getattr(new, "_compiled", False), "compile() new before migrating"

    # the destination model's telemetry session becomes the sink for the
    # migration's spans/events, exactly as compile/fit scope theirs
    session = getattr(new, "_telemetry", None)
    if session is not None:
        telemetry.activate(session)
    try:
        return _migrate_impl(old, new, plan=plan, donate=donate)
    finally:
        if session is not None:
            telemetry.deactivate(session)


def _migrate_impl(old, new, *, plan, donate: bool) -> dict:
    from .. import telemetry
    from ..analysis import transition as fftrans
    from .reshard import model_state_tree

    if plan is None:
        plan = fftrans.plan_model_transition(old, new)
    with telemetry.span("migrate.verify"):
        result = fftrans.gate_transition(plan, new.config,
                                         label="migrate_state")
    plan_json = plan.to_json(analysis=result)

    src_flat = {
        jtu.keystr(path): leaf
        for path, leaf in jtu.tree_flatten_with_path(
            model_state_tree(old))[0]}
    template = model_state_tree(new)
    flat_t, treedef = jtu.tree_flatten_with_path(template)

    t0 = time.perf_counter()
    moved = []
    leaves = []
    with telemetry.span("migrate.apply"):
        for path, tleaf in flat_t:
            key = jtu.keystr(path)
            src = src_flat.get(key)
            if src is None:
                # only reachable under --no-verify-plan (unmapped_state
                # was downgraded): keep the new model's fresh leaf
                leaves.append(tleaf)
                continue
            out = _move_leaf(src, tleaf)
            moved.append(out)
            leaves.append(out)
            if donate and hasattr(src, "delete") and out is not src:
                src.delete()
        restored = jtu.tree_unflatten(treedef, leaves)
        for leaf in moved:
            # one drain at the end of the migration — the measurement IS
            # the migration wall time, not a hot loop
            jax.block_until_ready(leaf)
    measured_s = time.perf_counter() - t0

    new._params = restored["params"]
    new._state = restored["state"] if restored["state"] else new._state
    new._opt_slots = restored["opt_slots"]
    new._step = restored["step"]
    new._counters = restored["counters"]
    new._rng = jax.random.wrap_key_data(
        jax.device_get(restored["rng"]).astype(np.uint32))
    if donate:
        old._compiled = False  # the old model's state buffers are dead

    plan_json["measured_s"] = measured_s
    if plan.predicted_s > 0 and measured_s > 0:
        # fidelity datapoint for the elastic payoff rule: fold this
        # migration's measured/predicted ratio into the per-device-kind
        # calibration entry (elastic/payoff.py — persisted via the
        # warm-start DB so it survives restarts)
        from ..elastic.payoff import record_fidelity

        record_fidelity(new, measured_s / plan.predicted_s)
    new._transition = plan_json
    telemetry.observe("migration_s", measured_s)
    telemetry.event(
        "migrate", predicted_s=plan.predicted_s, measured_s=measured_s,
        transfers=len(plan.transfers),
        bytes_on_wire=sum(plan.bytes_on_wire.values()),
        errors=len(result.errors()))
    _rewrite_report(new)
    return plan_json


def _rewrite_report(model) -> Optional[dict]:
    """Re-write strategy_report.json after a migration so the
    `transition` section lands next to the compile-time attribution
    (the diagnostics manager wrote the report before the migration
    existed). No-op without a telemetry session."""
    session = getattr(model, "_telemetry", None)
    if session is None:
        return None
    from ..diagnostics.explain import write_strategy_report

    try:
        return write_strategy_report(model, session.directory)
    except Exception:  # pragma: no cover - report must not fail a migrate
        return None
