"""The training job of a routed-expert LM (OLMoE): FFModel.compile ->
FFModel.fit on seeded batches, as jobs/train.py does it (whose fit call
this loads), with what an expert layer adds to the set-up and the checks.

Set-up: build the model from the configuration's published keys and the
cell's flags, warm the step with one short fit, compare the training
graph's logits with the reference (benchmarks/olmoe_reference.py). Routing
is discontinuous, so at the positions where the reference's 8th and 9th
router probabilities are a near-tie (olmoe_reference.TIE_MARGIN) the
reference is evaluated under the program's choice of experts. The
near-ties and those of them at which that choice differs from the
reference's own are counted and printed; a run in which the program's
choice was taken over the reference's at more than a quarter of the
positions is not correct. No position is left out of the comparison.
Window: fit calls back to back. After it: the step's counters (assignments
no expert computed: 0 in a dropless layer; the largest expert's load over
the mean). A traced run also leaves the step program's instructions by
expert-layer scope (moe_events.py).
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks import harness, moe_events, reference
from benchmarks import olmoe_reference
from benchmarks import traffic as traffic_gen

CHECK_POSITIONS = 256
train = harness.load_module("jobs", "train.py")


def lm_config(config: dict, sequence_length: int, attention_impl: str):
    """The program's TransformerLMConfig from the published keys
    (transformers' OlmoeConfig naming)."""
    from flexflow_tpu.models import olmoe_lm_config

    if sequence_length > config["max_position_embeddings"]:
        raise ValueError("the cell's sequences are longer than "
                         "max_position_embeddings")
    fixed = {   # what the program's OLMoE block does not vary
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "rope_scaling": None, "tie_word_embeddings": False,
        "norm_topk_prob": False,
        "num_key_value_heads": config["num_attention_heads"]}
    for key, only in fixed.items():
        if config[key] != only:
            raise ValueError(f"{key}={config[key]!r}: the program builds "
                             f"{only!r} only")
    return olmoe_lm_config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_layers=config["num_hidden_layers"],
        sequence_length=sequence_length, attention_impl=attention_impl,
        norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["intermediate_size"],
        router_aux_loss_coef=config["router_aux_loss_coef"])


def scoped_instructions(ff, x, y, batch: int) -> list:
    """[[instruction, scope]] of the step program's instructions inside
    the expert layer's scopes, for the readers of a traced run
    (moe_events.py): the step lowered and compiled again for the text. The
    compile cache has the program by now, and set-up pays the lowering."""
    import jax

    data = ff._make_batch({k: v[:batch] for k, v in x.items()}, y[:batch])
    _, rng = jax.random.split(ff._rng)
    text = ff.executor._train_step.lower(
        ff._params, ff._state, ff._opt_slots, ff._step, ff._counters, rng,
        data).compile().as_text()
    return moe_events.scoped_instructions(text)


def moe_state(ff, layers: int, key: str) -> list:
    return [ff._state[f"l{i}_moe"][key] for i in range(layers)]


def logit_check(ff, config, x, batch: int) -> dict:
    """The training graph's logits for the first CHECK_POSITIONS positions
    of the first seeded sequence against the reference's, the reference
    under the program's choice of experts at its near-ties."""
    n = min(CHECK_POSITIONS, x["tokens"].shape[1])
    layers = config["num_hidden_layers"]
    first = {k: v[:batch] for k, v in x.items()}
    ff.start_batch(first, np.zeros(first["tokens"].shape + (1,), np.int32))
    program = np.asarray(ff.forward()[0, :n], np.float32)
    ff._cached_logits = None    # the whole batch's logits; see jobs/train.py
    # a token's row in the op's (tokens, k) state is batch-major: the
    # first sequence's first n positions are its first n rows
    ids = [np.asarray(a[:n]) for a in moe_state(ff, layers, "expert_ids")]
    model = dict(num_layers=layers, num_heads=config["num_attention_heads"],
                 num_experts_per_tok=config["num_experts_per_tok"],
                 eps=config["rms_norm_eps"],
                 rope_theta=float(config["rope_theta"]))
    tokens, positions = x["tokens"][:1, :n], x["positions"][:1, :n]
    ref, routing = olmoe_reference.forward(
        ff._params, tokens, positions, program_ids=ids,
        tie_margin=olmoe_reference.TIE_MARGIN, **model)
    ties = taken = differ = 0
    widest = 0.0    # the widest gap the program chose otherwise across
    for mine, r in zip(ids, routing):
        k = mine.shape[1]
        tie = np.asarray(r["tie"])
        top = np.sort(np.asarray(r["probs"]), axis=1)[:, ::-1]
        gap = (top[:, k - 1] - top[:, k]) / top[:, k - 1]
        unlike = np.any(np.sort(mine, 1)
                        != np.sort(np.asarray(r["own_ids"]), 1), axis=1)
        ties, taken = ties + int(tie.sum()), taken + int((tie & unlike).sum())
        differ += int(unlike.sum())
        if unlike.any():
            widest = max(widest, float(gap[unlike].max()))
    return {"logit_error": reference.logit_error(program, np.asarray(ref)[0]),
            "near_ties": ties, "choice_taken": taken, "compared": n * layers,
            "routed_unlike": differ, "widest_swapped_gap": widest}


def run(ctx) -> dict:
    t, cell, config = ctx.traffic, ctx.cell, ctx.config
    seq, batch = t["sequence_length"], t["global_batch"]
    layers = config["num_hidden_layers"]
    steps = t["trace_steps_per_call" if ctx.trace_dir else "steps_per_call"]
    cfg = lm_config(config, seq, cell["attention_impl"])
    with ctx.span("ffcompile"):
        ff = harness.build_lm(
            cfg, [*cell["flags"], "--seed", str(ctx.seed % (2**31 - 1))],
            batch, cell["optimizer"])
    x, y = traffic_gen.train_batches(t, config["vocab_size"], ctx.seed, steps)
    warm = t["warmup_steps"] * batch
    with ctx.span("warmup"):
        warm_loss, warm_s = train.fit_call(
            ff, {k: v[:warm] for k, v in x.items()}, y[:warm], batch)
    print(f"[train_moe] warm-up of {t['warmup_steps']} steps: {warm_s:.2f} "
          f"s, mean loss {warm_loss:.4f}")
    with ctx.span("reference_check"):
        check = logit_check(ff, config, x, batch)
    taken_share = check["choice_taken"] / check["compared"]
    scoped = []
    if ctx.trace_dir:
        with ctx.span("scoped_instructions"):
            scoped = scoped_instructions(ff, x, y, batch)
    print(f"[train_moe] logits against the reference over "
          f"{CHECK_POSITIONS} positions: {check['logit_error']:.5f} of max "
          f"|logit| (tolerance {olmoe_reference.LOGIT_TOL}); "
          f"{check['near_ties']} of {check['compared']} positions are "
          f"near-ties of the router (margin {olmoe_reference.TIE_MARGIN}), "
          f"at {check['choice_taken']} of them the program's choice was "
          f"taken over the reference's (at most "
          f"{olmoe_reference.MAX_TAKEN_SHARE:.0%}); the program routed "
          f"{check['routed_unlike']} positions unlike the reference, the "
          f"widest gap it swapped across {check['widest_swapped_gap']:.5f}")

    calls = []
    t0 = ctx.open_window()
    while time.perf_counter() - t0 < ctx.seconds:
        with ctx.span("fit"):
            loss, dt = train.fit_call(ff, x, y, batch)
        calls.append((steps, dt, loss))
    ctx.close_window()

    done = sum(c[0] for c in calls)
    bad = sum(c[0] for c in calls if not math.isfinite(c[2]))
    dropped = sum(float(a) for a in moe_state(ff, layers, "dropped_tokens"))
    load = max(float(a) for a in moe_state(ff, layers, "load_max_over_mean"))
    print(f"[train_moe] {len(calls)} fit calls of {steps} steps in "
          f"{ctx.window_s:.2f} s; mean losses "
          f"{[round(c[2], 4) for c in calls]}; last step: {dropped:.0f} "
          f"assignments dropped, largest expert's load {load:.3f} x the mean")
    return {
        "attempted": done, "failed": bad,
        "correct": bool(check["logit_error"] <= olmoe_reference.LOGIT_TOL
                        and taken_share <= olmoe_reference.MAX_TAKEN_SHARE
                        and dropped == 0 and bad == 0
                        and math.isfinite(warm_loss)),
        "end_to_end": {"train_tok_s": done * batch * seq / ctx.window_s},
        "counters": {
            "steps": done, "tokens": done * batch * seq,
            "call_step_s": [c[1] / c[0] for c in calls],
            "dropped_tokens": dropped, "load_max_over_mean": load,
            "mesh": {k: int(v) for k, v in ff.mesh.shape.items()},
            "moe_instructions": scoped,
            **check,
        },
    }
