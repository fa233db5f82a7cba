"""Disaggregated prefill/decode serving (DistServe / Splitwise class).

Prefill is compute-bound (one long matmul-heavy chunk per request),
decode is memory-bound (one tiny batched step per token); co-locating
them makes every long prompt a TBT spike for every in-flight decode.
This coordinator splits the chip budget into two DISJOINT sub-meshes —
`mesh_device_offset` + `mesh_axis_sizes` config overrides carve
device windows — and compiles TWO Unity plans, one per role, each
priced and placed by its own search over its own sub-mesh (the role
joins the warm-start fingerprint, so the two plans cache
independently).

A request's life: prefill-side engine runs the full prompt (its own
radix prefix cache shortens repeated prefixes) and samples the FIRST
token; the coordinator lifts the prompt-extent KV blocks off the
prefill pools (the pre-release hook fires while the page table still
maps them), then hands the request to the decode engine, which maps
any decode-side radix-cached prefix for free, injects only the
uncovered block extent through one donated executable, and decodes to
completion. Every handoff is an fftrans transfer program — the
host-staged rows are modeled as `host_hop` collectives, verified by
`verify_transition` and priced by the SAME machine-model oracle as
every other collective — with measured-vs-predicted recorded per
handoff in the strategy report (`run_doctor --check` re-verifies the
makespan identity from the report alone).

The elastic tier gets a third trigger: when prefill queue-wait p95 and
decode TBT p95 diverge, the coordinator proposes a one-notch
chip-ratio shift, prices the two-sided re-plan, and gates it through
the SAME payoff inequality as every other migration
(`lhs = predicted_migration_s x fidelity_ratio < benefit x horizon`);
an approved shift shrinks one side's mesh first, then grows the other
into the freed window via `replan_mesh` — verified, priced state
migration per side, in-flight requests riding through untouched.
"""

from __future__ import annotations

import time
from typing import Optional, Sequence

from .. import telemetry
from .engine import ServingEngine
from .scheduler import Request


def sub_mesh_axes(model, n: int) -> tuple:
    """The n-chip sub-mesh factorization of `model`'s configured mesh:
    rescale the data axis, every other axis kept — the same shape
    discipline the elastic capacity trigger uses, so a sub-mesh plan is
    always a shape the search already prices. Shared by the
    disaggregated (prefill/decode) and speculative (drafter/target)
    engines — both carve disjoint device windows with it."""
    from ..machine import AXIS_DATA, DEFAULT_AXES

    ms = model.config.mesh_shape()
    sizes = list(int(s) for s in ms.axis_sizes)
    names = list(ms.axis_names)
    if len(names) != len(DEFAULT_AXES):
        raise ValueError(
            "sub-mesh serving runs single-host for now "
            "(multi-host meshes carry a dcn axis)")
    di = names.index(AXIS_DATA)
    fixed = 1
    for i, s in enumerate(sizes):
        if i != di:
            fixed *= s
    if n % fixed:
        raise ValueError(
            f"{n} chips cannot keep the non-data axes "
            f"(product {fixed}) of mesh {tuple(sizes)}")
    sizes[di] = n // fixed
    return tuple(sizes)


class DisaggregatedServingEngine:
    """Two ServingEngines on disjoint device windows + the KV handoff
    plane between them. Mirrors the ServingEngine surface (submit /
    step / run_until_drained / generate / stats / metrics_summary) so
    drivers swap in with one flag."""

    def __init__(self, model, prefill_chips: Optional[int] = None,
                 **overrides):
        import jax

        from .decode_graph import HANDOFF, refuse

        refuse(model, "serving/disagg.py: disaggregated serving (the "
               "handoff carries a prompt's whole extent, as blocks of keys "
               "and of values)", HANDOFF)
        cfg = model.config
        self.model = model
        self._total_chips = len(jax.devices())
        if prefill_chips is None:
            prefill_chips = int(getattr(cfg, "serve_prefill_chips", 0))
        if not prefill_chips:
            prefill_chips = self._total_chips // 2
        if not 0 < prefill_chips < self._total_chips:
            raise ValueError(
                f"serve(disaggregate=True) needs 1..{self._total_chips - 1} "
                f"prefill chips out of {self._total_chips}, got "
                f"{prefill_chips}")
        if overrides.get("kv_layout", cfg.serve_kv_layout) != "paged":
            raise ValueError(
                "disaggregated serving requires the paged KV layout "
                "(the handoff moves pool blocks)")
        self.prefill_chips = int(prefill_chips)
        user_over = dict(overrides.pop("config_overrides", None) or {})
        self.prefill = self._build_side(
            "prefill", 0, self.prefill_chips, user_over, overrides)
        self.decode = self._build_side(
            "decode", self.prefill_chips, self.decode_chips, user_over,
            overrides)
        # prefill completes every request after ONE token; the hook
        # lifts the KV while the page table still maps it, and the
        # suppression keeps completion accounting single-sourced on the
        # decode side (doctor's drained-TTFT identity counts each
        # request exactly once)
        self.prefill._pre_release_hook = self._on_prefill_done
        self.prefill._suppress_completion_events = True
        self._machine = self._build_machine()
        self._kv_stash: dict[int, tuple] = {}  # request_id -> (k, v, s)
        self._pending: list[Request] = []  # prefilled, awaiting a slot
        self.handoffs: list[dict] = []
        self._programs: dict[int, dict] = {}  # injected blocks -> plan
        self._plan_cache: dict[int, tuple] = {}
        self._rebalance_decisions: list[dict] = []
        self.completed: list[Request] = []
        self._iterations = 0
        self.rebalance_min_samples = 8
        self.rebalance_factor = 1.5
        # the two pools live on DISJOINT devices, so their steps really
        # do run concurrently: one worker thread drives the prefill
        # engine while the coordinator thread drives decode — without
        # it, every in-flight decode dispatch serializes in front of
        # every waiting prefill and TTFT inherits the decode tail
        from concurrent.futures import ThreadPoolExecutor

        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="ff-serve-prefill")

    def _build_side(self, role: str, offset: int, chips: int,
                    user_over: dict, overrides: dict) -> ServingEngine:
        over = dict(user_over)
        over["mesh_axis_sizes"] = self._sub_axes(chips)
        over["mesh_device_offset"] = int(offset)
        return ServingEngine(self.model, role=role,
                             config_overrides=over, **overrides)

    def _build_machine(self):
        from ..search.machine_model import machine_model_for_mesh

        return machine_model_for_mesh(
            self.decode.decode_model.mesh,
            num_hosts=self.model.config.num_nodes)

    @property
    def decode_chips(self) -> int:
        return self._total_chips - self.prefill_chips

    def _sub_axes(self, n: int) -> tuple:
        return sub_mesh_axes(self.model, n)

    # ------------------------------------------------------------ intake

    def submit(self, prompt: Sequence[int], **request_kw) -> Request:
        """Enqueue on the prefill side, clamped to ONE generated token
        there — the first token is the prefill pool's last output; the
        rest of the budget decodes on the decode pool."""
        req = self.prefill.submit(prompt, **request_kw)
        req._disagg_max_new = req.max_new_tokens
        req.max_new_tokens = 1
        return req

    def _on_prefill_done(self, slot, req: Request):
        """Pre-release hook on the prefill engine: the slot's page
        table still maps the prompt blocks, so lift them now."""
        t0 = time.perf_counter()
        ks, vs = self.prefill.extract_kv(slot.index, len(req.prompt))
        # this pair is the extract half of the handoff's measured_s —
        # it reaches the metrics plane via _record_handoff, and a span
        # here would double-record every handoff
        self._kv_stash[req.request_id] = (
            ks, vs, time.perf_counter() - t0)  # fflint: ok raw_timer_in_hot_path

    # ------------------------------------------------------------ iterate

    def step(self) -> list[Request]:
        """One coordinator iteration: the prefill and decode engine
        steps run CONCURRENTLY (disjoint device windows — the worker
        thread prefills while this thread decodes, so a long prompt is
        never a TBT spike and an in-flight decode batch never delays a
        waiting prefill), then handoff routing and decode-side
        admissions (FCFS, head-blocking — a full decode batch never
        reorders the handoff queue). Returns the requests that
        completed. The session activation is held across the overlap so
        the inner engines' nested activate/deactivate pairs (either
        thread) cannot tear the telemetry sink down mid-step."""
        done: list[Request] = []
        tel = self.decode.telemetry
        if tel is not None:
            telemetry.activate(tel)
        try:
            fut = self._pool.submit(self.prefill.step)
            dec_done = self.decode.step()
            pre_done = fut.result()
        finally:
            if tel is not None:
                telemetry.deactivate(tel)
        for req in pre_done:
            done.extend(self._route_prefilled(req))
        while self._pending:
            if not self._admit_handoff(self._pending[0]):
                break
            self._pending.pop(0)
        done.extend(dec_done)
        self._iterations += 1
        self.completed.extend(done)
        return done

    def _route_prefilled(self, req: Request) -> list[Request]:
        """Classify one prefill completion: truly finished (EOS on the
        first token, a one-token budget, or a full cache) is recorded
        on the decode side and returned; everything else joins the
        handoff queue with its real token budget restored."""
        real = getattr(req, "_disagg_max_new", req.max_new_tokens)
        req.max_new_tokens = real
        if req.finish_reason == "max_tokens" and real > len(req.generated):
            if len(req.prompt) >= self.decode.max_seq_len:
                # the decode cache has no row for a second token — the
                # same "length" verdict the unified engine reaches
                req.finish_reason = "length"
            else:
                req.finished = False
                req.finish_reason = ""
                req.finish_t = None
                self._pending.append(req)
                return []
        self.decode.scheduler.completed.append(req)
        with self.decode._active():
            self.decode.record_completion(req)
        return [req]

    def _admit_handoff(self, req: Request) -> bool:
        """Try to land one prefilled request on the decode pool; False
        means no slot/reservation (retry next step, order kept)."""
        ks, vs, extract_s = self._kv_stash[req.request_id]
        t0 = time.perf_counter()
        injected = self.decode.admit_prefilled(
            req, req.generated[-1], ks, vs)
        if injected is None:
            return False
        measured = extract_s + (time.perf_counter() - t0)
        del self._kv_stash[req.request_id]
        self._record_handoff(req, injected, measured)
        return True

    # ------------------------------------------------------------ handoff plane

    def _record_handoff(self, req: Request, injected: int,
                        measured_s: float):
        bs = self.decode.block_manager.block_size
        nlb = -(-len(req.prompt) // bs)
        predicted = 0.0
        if injected > 0:
            prog = self._handoff_program(injected)
            predicted = float(prog["predicted_s"])
        rec = {
            "request_id": req.request_id,
            "prompt_tokens": len(req.prompt),
            "prompt_blocks": nlb,
            "matched_prefix_len": req.matched_prefix_len,
            "injected_blocks": int(injected),
            "predicted_s": predicted,
            "measured_s": float(measured_s),
        }
        self.handoffs.append(rec)
        with self.decode._active():
            telemetry.event("serve.handoff", **rec)

    def _handoff_program(self, nblk: int) -> dict:
        """The verified, priced fftrans transfer program for an
        nblk-block handoff — built once per distinct block count (the
        program depends only on the extent): per-layer host-resident
        (nblk, block, embed) K/V leaves on the prefill side hop through
        the host NIC into the decode pools' sharding, exactly the
        device_get -> device_put the implementation performs."""
        cached = self._programs.get(nblk)
        if cached is not None:
            return cached
        from ..analysis.transition import (
            LeafInfo, PlanSide, build_transition_plan, verify_transition,
            _assignment_of_leaf)

        bs = self.decode.block_manager.block_size
        dec = self.decode.decode_model
        src = PlanSide(axis_sizes={
                           k: int(v) for k, v
                           in dict(self.prefill.decode_model.mesh
                                   .shape).items()},
                       plan_source="serve_prefill", kv_block_size=bs,
                       on_device=False, label="prefill_kv")
        dst = PlanSide(axis_sizes={k: int(v) for k, v
                                   in dict(dec.mesh.shape).items()},
                       plan_source=dec._plan_source, kv_block_size=bs,
                       on_device=True, label="decode_kv")
        for i, (name, *parts) in enumerate(self.decode._handoff_leaves):
            for part in parts:
                pool = dec._state[name][part]
                key = f"['{name}']['{part}']"
                shape = (int(nblk), int(pool.shape[1]),
                         int(pool.shape[2]))
                src.leaves[key] = LeafInfo(
                    key=key, shape=shape, dtype=str(pool.dtype),
                    assignment=None, kv_pool=True, topo_pos=i)
                dst.leaves[key] = LeafInfo(
                    key=key, shape=shape, dtype=str(pool.dtype),
                    assignment=_assignment_of_leaf(pool), kv_pool=True,
                    topo_pos=i)
        plan = build_transition_plan(src, dst, machine=self._machine)
        analysis = verify_transition(plan)
        prog = plan.to_json(analysis)
        self._programs[nblk] = prog
        return prog

    # ------------------------------------------------------------ rebalance

    def propose_ratio_shift(self) -> Optional[dict]:
        """The prefill:decode ratio trigger: when prefill queue-wait
        p95 and decode TBT p95 diverge past `rebalance_factor`, propose
        the next feasible one-notch boundary shift toward the starved
        side. Pure observation — no state changes."""
        from ..telemetry.metrics import percentile_from_hist

        qwh = self.prefill._h_queue_wait
        tbth = self.decode._h_tbt
        if (qwh.count < self.rebalance_min_samples
                or tbth.count < self.rebalance_min_samples):
            return None
        qw = percentile_from_hist(qwh.to_dict(), 95)
        tbt = percentile_from_hist(tbth.to_dict(), 95)
        if qw > self.rebalance_factor * tbt:
            direction = 1  # queue backs up at prefill: grow prefill
        elif tbt > self.rebalance_factor * qw:
            direction = -1  # decode batch starves: grow decode
        else:
            return None
        new_p = self._next_split(direction)
        if new_p is None:
            return None
        return {"new_prefill_chips": new_p, "queue_wait_p95_s": qw,
                "tbt_p95_s": tbt, "direction": direction}

    def _next_split(self, direction: int) -> Optional[int]:
        cand = self.prefill_chips + direction
        while 0 < cand < self._total_chips:
            try:
                self._sub_axes(cand)
                self._sub_axes(self._total_chips - cand)
                return cand
            except ValueError:
                cand += direction
        return None

    def maybe_rebalance(self, horizon_steps: int = 256,
                        forced: bool = False) -> Optional[dict]:
        """Price a proposed ratio shift through the payoff inequality
        and execute it when (and only when) migration pays for itself
        within the horizon — the serving twin of the training-side
        drift/capacity triggers, producing the SAME decision-record
        shape `run_doctor --check` reproduces arithmetic from."""
        from ..elastic.payoff import evaluate_payoff, load_fidelity

        prop = self.propose_ratio_shift()
        if prop is None:
            return None
        fidelity, samples = load_fidelity(self.model)
        benefit = abs(prop["queue_wait_p95_s"] - prop["tbt_p95_s"])
        decision = {
            "trigger": "serve_ratio", "scope": "serving_disagg",
            "old_prefill_chips": self.prefill_chips,
            "fidelity_samples": samples,
        }
        decision.update(prop)
        decision.update(evaluate_payoff(
            predicted_migration_s=self._predict_rebalance_s(
                prop["new_prefill_chips"]),
            fidelity_ratio=fidelity,
            benefit_s_per_step=benefit,
            horizon_steps=horizon_steps,
            forced=forced))
        if decision["would_migrate"] or forced:
            t0 = time.perf_counter()
            self._set_split(prop["new_prefill_chips"])
            decision["decision"] = "migrated"
            decision["migration_measured_s"] = time.perf_counter() - t0
        else:
            decision["decision"] = "declined"
        with self.decode._active():
            telemetry.event("replan", **decision)
        self._rebalance_decisions.append(decision)
        # ride the elastic report section so the doctor's payoff gate
        # covers ratio decisions with zero new plumbing
        if not hasattr(self.model, "_elastic_decisions"):
            self.model._elastic_decisions = []
        self.model._elastic_decisions.append(decision)
        return decision

    def _predict_rebalance_s(self, new_p: int) -> float:
        """Priced cost of re-planning BOTH sides: each side's full
        decode state (params + pools) staged through the host NIC —
        the conservative cross-window figure, priced by the same
        oracle as the handoff programs."""
        from ..search.cost_model import price_transfer_collective

        total = 0.0
        for eng in (self.prefill, self.decode):
            b = 0.0
            for ws in eng.decode_model._state.values():
                for arr in ws.values():
                    b += float(arr.size) * arr.dtype.itemsize
            total += price_transfer_collective(
                "host_hop", b, b, "", self._machine)
        return total

    def _set_split(self, new_p: int):
        """Move the chip boundary: the shrinking side re-plans FIRST
        (its new window is a subset of its old one), then the growing
        side expands into the freed devices — the two windows stay
        disjoint at every instant."""
        total = self._total_chips
        if new_p < self.prefill_chips:
            order = [(self.prefill, new_p, 0),
                     (self.decode, total - new_p, new_p)]
        else:
            order = [(self.decode, total - new_p, new_p),
                     (self.prefill, new_p, 0)]
        for eng, chips, offset in order:
            eng.spec.config_overrides = dict(
                eng.spec.config_overrides or {})
            eng.spec.config_overrides["mesh_device_offset"] = int(offset)
            eng.replan_mesh(self._sub_axes(chips), trigger="serve_ratio")
        self.prefill_chips = int(new_p)
        self._machine = self._build_machine()
        self._plan_cache.clear()
        self._programs.clear()  # re-priced against the new decode mesh

    # ------------------------------------------------------------ drain

    @property
    def drained(self) -> bool:
        return (self.prefill.scheduler.drained
                and self.decode.scheduler.drained
                and not self._pending)

    def run_until_drained(self, max_iterations: int = 0) -> list[Request]:
        done: list[Request] = []
        t0 = time.perf_counter()
        it = 0
        while not self.drained:
            done.extend(self.step())
            it += 1
            if max_iterations and it >= max_iterations:
                break
        self.note_drain(time.perf_counter() - t0)
        return done

    def note_drain(self, wall_s: float):
        """Close one measured window: ONE merged summary event, ONE
        drained metrics snapshot (both engines' registries are attached
        to the same session, so the snapshot merges the pair), and the
        strategy report's serving_disagg section rewritten in place."""
        self.prefill._last_wall_s = wall_s
        self.decode._last_wall_s = wall_s
        with self.decode._active():
            telemetry.event("serve.summary", **self.metrics_summary())
        tel = self.decode.telemetry
        if tel is not None:
            tel.write_metrics_snapshot(reason="serve_drain",
                                       drained=bool(self.drained))
            tel.flush()
        self._update_report()

    def _update_report(self):
        self.model._serving_disagg = self.disagg_section()
        diag = getattr(self.model, "_diagnostics", None)
        if diag is not None and getattr(diag, "report", None):
            from ..diagnostics.explain import rewrite_strategy_report

            diag.report["serving_disagg"] = self.model._serving_disagg
            rewrite_strategy_report(diag.report, diag.directory)

    def generate(self, prompts: Sequence[Sequence[int]],
                 **request_kw) -> list[list[int]]:
        reqs = [self.submit(p, **request_kw) for p in prompts]
        self.run_until_drained()
        return [r.generated for r in reqs]

    # ------------------------------------------------------------ stats

    def disagg_section(self) -> dict:
        """The strategy report's `serving_disagg` section: split
        geometry, every handoff's measured-vs-predicted, the distinct
        verified transfer programs they reference (keyed by injected
        block count), and the ratio-trigger decision log. run_doctor
        --check recomputes each program's predicted_s from its own
        transfer entries and requires every handoff to reproduce it."""
        n = len(self.handoffs)
        return {
            "prefill_chips": self.prefill_chips,
            "decode_chips": self.decode_chips,
            "prefill_mesh_axes": {
                k: int(v) for k, v
                in dict(self.prefill.decode_model.mesh.shape).items()},
            "decode_mesh_axes": {
                k: int(v) for k, v
                in dict(self.decode.decode_model.mesh.shape).items()},
            "handoffs": list(self.handoffs),
            "programs": {str(k): v for k, v in self._programs.items()},
            "summary": {
                "count": n,
                "predicted_s": sum(h["predicted_s"]
                                   for h in self.handoffs),
                "measured_s": sum(h["measured_s"]
                                  for h in self.handoffs),
                "fully_cached": sum(1 for h in self.handoffs
                                    if h["injected_blocks"] == 0),
            },
            "rebalances": list(self._rebalance_decisions),
        }

    def stats(self) -> dict:
        pre = self.prefill.stats()
        dec = self.decode.stats()
        out = {
            "disaggregated": True,
            "prefill_chips": self.prefill_chips,
            "decode_chips": self.decode_chips,
            "num_chips": self._total_chips,
            "requests_completed": dec["requests_completed"],
            "handoffs": len(self.handoffs),
            "handoff_predicted_s": sum(h["predicted_s"]
                                       for h in self.handoffs),
            "handoff_measured_s": sum(h["measured_s"]
                                      for h in self.handoffs),
            "pending_handoffs": len(self._pending),
            "prefill": pre,
            "decode": dec,
        }
        wall = getattr(self.decode, "_last_wall_s", 0.0) or 0.0
        if wall > 0:
            out["requests_per_sec_per_chip"] = (
                dec["requests_completed"] / wall / self._total_chips)
        return out

    def metrics_summary(self) -> dict:
        out = self.stats()
        out["prefill"] = self.prefill.metrics_summary()
        out["decode"] = self.decode.metrics_summary()
        return out

    def reset_stats(self) -> None:
        self.prefill.reset_stats()
        self.decode.reset_stats()
        self.completed.clear()
        self.handoffs.clear()
        self._iterations = 0
