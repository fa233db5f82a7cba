"""The training job: FFModel.compile -> FFModel.fit on seeded batches, in
whole calls of `steps_per_call` optimizer steps until the window is up.

Set-up: build and compile the model from the cell's flags, warm the step
with one short fit, compare the training graph's logits with the
reference. Window: fit calls back to back, each timed to the end of the
device's work; only whole calls count, and the rate is all their tokens
over all the window's seconds.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks import harness, reference
from benchmarks import traffic as traffic_gen

CHECK_POSITIONS = 256


def fit_call(ff, x, y, batch: int):
    """(mean loss, seconds) of one FFModel.fit over x, y, timed to the end
    of the device's work."""
    import jax

    ff.reset_metrics()
    t0 = time.perf_counter()
    ff.fit(x, y, epochs=1, batch_size=batch, shuffle=False, verbose=False)
    jax.block_until_ready(ff._params)
    dt = time.perf_counter() - t0
    return float(ff.get_perf_metrics().get_mean_loss()), dt


def logit_error(ff, ctx, x, batch: int) -> float:
    """The training graph's logits for the first CHECK_POSITIONS positions
    of the first seeded sequence against the reference's."""
    n = min(CHECK_POSITIONS, x["tokens"].shape[1])
    first = {k: v[:batch] for k, v in x.items()}
    ff.start_batch(first, np.zeros(first["tokens"].shape + (1,), np.int32))
    program = np.asarray(ff.forward()[0, :n], np.float32)
    # forward() keeps the whole batch's logits on the model (gigabytes at
    # these sizes); the training steps need that memory
    ff._cached_logits = None
    ref = reference.forward_logits(
        harness.param_getter(ff), x["tokens"][:1, :n],
        num_layers=ctx.config["n_layer"], num_heads=ctx.config["n_head"])[0]
    return reference.logit_error(program, ref)


def run(ctx) -> dict:
    t, cell = ctx.traffic, ctx.cell
    seq, batch = t["sequence_length"], t["global_batch"]
    steps = t["trace_steps_per_call" if ctx.trace_dir else "steps_per_call"]
    cfg = harness.lm_config(ctx.config, seq, cell["attention_impl"])
    with ctx.span("ffcompile"):
        ff = harness.build_lm(
            cfg, [*cell["flags"], "--seed", str(ctx.seed % (2**31 - 1))],
            batch, cell["optimizer"])
    x, y = traffic_gen.train_batches(t, ctx.config["vocab_size"], ctx.seed,
                                     steps)
    warm = t["warmup_steps"] * batch
    with ctx.span("warmup"):
        warm_loss, warm_s = fit_call(
            ff, {k: v[:warm] for k, v in x.items()}, y[:warm], batch)
    print(f"[train] warm-up of {t['warmup_steps']} steps: {warm_s:.2f} s, "
          f"mean loss {warm_loss:.4f}")
    with ctx.span("reference_check"):
        err = logit_error(ff, ctx, x, batch)
    print(f"[train] logits against the reference over {CHECK_POSITIONS} "
          f"positions: {err:.5f} of max |logit| (tolerance "
          f"{reference.LOGIT_TOL})")

    calls = []
    t0 = ctx.open_window()
    while time.perf_counter() - t0 < ctx.seconds:
        with ctx.span("fit"):
            loss, dt = fit_call(ff, x, y, batch)
        calls.append((steps, dt, loss))
    ctx.close_window()

    done = sum(c[0] for c in calls)
    bad = sum(c[0] for c in calls if not math.isfinite(c[2]))
    print(f"[train] {len(calls)} fit calls of {steps} steps in "
          f"{ctx.window_s:.2f} s; mean losses "
          f"{[round(c[2], 4) for c in calls]}")
    upd = getattr(ff, "_update_sharding", None) or {}
    return {
        "attempted": done, "failed": bad,
        "correct": bool(err <= reference.LOGIT_TOL and bad == 0
                        and math.isfinite(warm_loss)),
        "end_to_end": {"train_tok_s": done * batch * seq / ctx.window_s},
        "counters": {
            "steps": done, "tokens": done * batch * seq,
            "call_step_s": [c[1] / c[0] for c in calls],
            "logit_error": err,
            "mesh": {k: int(v) for k, v in ff.mesh.shape.items()},
            "update_sharding": (f"stage {upd.get('stage')}"
                                if upd.get("enabled") else "replicated"),
        },
    }
