"""The apply half of elastic re-planning: recompile in place, price, gate,
then migrate or roll back — one step boundary, no restart.

`replan(model, ...)` is the controller's workhorse. It snapshots the live
plan + training state, recompiles the SAME FFModel object through the
normal compile pipeline (warm-start cache consulted first, host-0 search +
broadcast in multihost runs, the full ffcheck/ffsan/ffrules verifier gate
— the new plan is a first-class plan source, labeled `replan`), prices the
old→new move with fftrans, evaluates the payoff inequality, and either
executes `migrate_state` (bit-exact, verified) or restores the snapshot as
if nothing happened. Every path — migrated, declined, dry-run, failed —
appends a decision record carrying both sides of the inequality to
`model._elastic_decisions`, emits a `replan` telemetry event, and lands in
strategy_report.json's `elastic` section.

Telemetry note: `model.compile()` and `migrate_state` both deactivate the
process-wide telemetry sink in their finallys (they assume they own the
session window). A mid-fit replan runs INSIDE fit's window, so this module
re-activates the saved session after each of those calls — otherwise the
rest of the fit would silently stop recording.
"""

from __future__ import annotations

import copy
import time
from typing import Optional

from ..telemetry import log as fflog
from .payoff import evaluate_payoff, load_fidelity

# everything a compile writes on the model, plus the live training state
# migrate_state moves: enough for the snapshot to satisfy the `old` model
# contract of PlanSide.from_model / model_state_tree / migrate_state, and
# for restore() to make a declined replan invisible
_SNAP_ATTRS = (
    "graph", "mesh", "executor", "optimizer", "loss_type", "metrics",
    "label_spec",
    "_strategy", "_plan_source", "_plan_fingerprint", "_plan_record",
    "_update_sharding", "_search_result", "_replay_search", "_analysis",
    "_spmd_barrier", "_transition", "_predicted_step_s",
    "_params", "_state", "_opt_slots", "_step", "_counters", "_rng",
)


class PlanSnapshot:
    """Frozen capture of a compiled model's plan and live state.

    Quacks like a compiled FFModel for fftrans's PlanSide.from_model and
    resilience.migrate_state's `old` argument (attribute surface: mesh,
    graph, executor, config, _update_sharding, _plan_source, the live
    state leaves), and restores every captured attribute for the
    rollback path."""

    def __init__(self, model):
        self._model_config = model.config  # shared object, never replaced
        for a in _SNAP_ATTRS:
            setattr(self, a, getattr(model, a, None))
        # config is copied so the snapshot keeps the OLD mesh_axis_sizes
        # (PlanSide reads config.num_nodes / serve_kv_block_size off it)
        self.config = copy.copy(model.config)
        self._compiled = True

    def restore(self, model):
        """Put every captured attribute back on the model; the config
        object is shared, so only the field replan mutates is reset."""
        for a in _SNAP_ATTRS:
            setattr(model, a, getattr(self, a))
        model.config.mesh_axis_sizes = self.config.mesh_axis_sizes
        model._compiled = True


def _reset_plan_state(model):
    """Clear plan residue so _compile_impl runs a fresh plan decision
    (plan source branches key off these; a stale _plan_source would
    short-circuit the search)."""
    model._strategy = None
    model._plan_source = "none"
    model._plan_fingerprint = None
    model._plan_record = None
    model._search_result = None
    model._replay_search = None
    model._transition = None


def replan(model, *, step: int, trigger: str,
           horizon_steps: int, new_mesh_axes: Optional[tuple] = None,
           measured_ema_s: Optional[float] = None, dry_run: bool = False,
           forced: bool = False, extra: Optional[dict] = None) -> dict:
    """One full re-plan attempt at a step boundary; returns the decision
    record (also appended to `model._elastic_decisions`).

    decision ∈ migrated | declined | dry_run | failed. The payoff rule:
    migrate iff predicted_migration_s × fidelity_ratio <
    benefit_s_per_step × horizon_steps, where benefit is the measured
    step-time EMA (falling back to the old plan's prediction) minus the
    new plan's predicted makespan. `forced` (capacity shrink) records
    the inequality but migrates regardless — the compiled mesh no
    longer exists. Declined/dry-run/failed paths restore the snapshot
    bit-exactly."""
    from .. import telemetry
    from ..analysis import transition as fftrans
    from ..diagnostics.drift import recalibrate_model
    from ..resilience.migrate import migrate_state

    session = telemetry.active_session()
    t0 = time.perf_counter()
    decision: dict = {
        "step": int(step), "trigger": str(trigger),
        "dry_run": bool(dry_run),
    }
    if extra:
        decision.update(extra)
    snap = PlanSnapshot(model)
    decision["old_mesh_axes"] = {k: int(v)
                                 for k, v in snap.mesh.shape.items()}
    decision["old_predicted_step_s"] = snap._predicted_step_s
    decision["measured_ema_s"] = measured_ema_s
    migrated = False
    rolled_back = False
    try:
        with telemetry.span("elastic.replan", trigger=trigger, step=step):
            if trigger == "drift":
                # the monitor fired BECAUSE the calibration no longer
                # describes the device: refresh it (and the warm-start
                # DB, coordinator-only) so the re-search prices real
                # costs — and so the plan-cache fingerprint moves off
                # the stale entries
                recalibrate_model(model)
            t_search0 = time.perf_counter()
            _reset_plan_state(model)
            if new_mesh_axes is not None:
                model.config.mesh_axis_sizes = tuple(new_mesh_axes)
            # relabel the recompile's outcome as plan_source "replan"
            # (the underlying origin — search/cache/broadcast — rides
            # the decision record as plan_origin)
            model._plan_source_hint = "replan"
            model.compile(
                optimizer=snap.optimizer, loss_type=snap.loss_type,
                metrics=getattr(model, "_metrics_arg", ()) or (),
                comp_mode=model.config.computation_mode)
        if session is not None:
            telemetry.activate(session)  # compile() deactivated it
        decision["research_s"] = time.perf_counter() - t_search0
        decision["plan_origin"] = getattr(model, "_plan_origin", None)
        decision["new_mesh_axes"] = {
            k: int(v) for k, v in model.mesh.shape.items()}
        decision["new_predicted_step_s"] = model._predicted_step_s
        plan = fftrans.plan_model_transition(snap, model)
        ratio, nsamples = load_fidelity(model)
        baseline = (float(measured_ema_s) if measured_ema_s
                    else float(snap._predicted_step_s or 0.0))
        benefit = max(0.0, baseline - float(model._predicted_step_s or 0.0))
        decision.update(evaluate_payoff(
            predicted_migration_s=plan.predicted_s, fidelity_ratio=ratio,
            benefit_s_per_step=benefit, horizon_steps=horizon_steps,
            forced=forced))
        decision["fidelity_samples"] = nsamples
        if (decision["would_migrate"] or forced) and not dry_run:
            # gate_transition runs inside migrate_state; a verification
            # failure raises and rolls back below
            migrate_state(snap, model, plan=plan)
            if session is not None:
                telemetry.activate(session)  # migrate_state deactivated it
            migrated = True
            decision["decision"] = "migrated"
            decision["migration_measured_s"] = (
                model._transition or {}).get("measured_s")
        else:
            decision["decision"] = "dry_run" if dry_run else "declined"
            snap.restore(model)
            rolled_back = True
    except Exception as e:
        snap.restore(model)
        rolled_back = True
        if session is not None:
            telemetry.activate(session)
        decision["decision"] = "failed"
        decision["error"] = f"{type(e).__name__}: {e}"
        fflog.error("elastic: replan failed (%s) — rolled back to the "
                    "running plan: %s", trigger, decision["error"])
    decision["total_s"] = time.perf_counter() - t0
    if not hasattr(model, "_elastic_decisions"):
        model._elastic_decisions = []
    model._elastic_decisions.append(decision)
    _finalize_artifacts(model, decision, rolled_back=rolled_back)
    return decision


def _finalize_artifacts(model, decision: dict, *, rolled_back: bool):
    """Record the decision everywhere run_doctor looks: a `replan`
    telemetry event, an alert record, and a strategy_report rewrite so
    the `elastic` section includes this decision (on rollback, the
    report also reverts to the restored plan and the drift monitor
    re-arms at its prediction)."""
    from .. import telemetry

    if telemetry.active_session() is not None:
        telemetry.inc("elastic_replan_decisions_total",
                      decision=str(decision.get("decision", "unknown")),
                      trigger=str(decision.get("trigger", "unknown")))
        telemetry.event("replan", **decision)
    else:
        # direct replan() call outside a fit window: land the event in
        # the model's own session so run_doctor still sees it
        tel = getattr(model, "_telemetry", None)
        if tel is not None:
            tel.recorder.record("replan", **decision)
    diag = getattr(model, "_diagnostics", None)
    if diag is not None:
        msg = (f"elastic {decision['trigger']} trigger at step "
               f"{decision['step']}: {decision['decision']}"
               + (f" (lhs {decision['lhs_s'] * 1e3:.3f} ms vs rhs "
                  f"{decision['rhs_s'] * 1e3:.3f} ms)"
                  if "lhs_s" in decision else "")
               + (f" [{decision['error']}]"
                  if "error" in decision else ""))
        diag._alerts.record(
            "alert", rule="elastic_replan", level="warning",
            step=decision["step"], action=decision["decision"],
            message=msg)
        fflog.warning("diagnostics[elastic_replan]: %s", msg)
    if rolled_back:
        if diag is not None:
            # rewrite the report for the RESTORED plan (elastic section
            # included) and re-arm the drift monitor at its prediction
            diag.on_compile()
    else:
        session = getattr(model, "_telemetry", None)
        if session is not None:
            from ..diagnostics.explain import write_strategy_report

            try:
                write_strategy_report(model, session.directory)
            except Exception:  # pragma: no cover - report best-effort
                pass
