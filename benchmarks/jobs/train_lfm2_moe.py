"""The training job of LFM2-MoE (LFM2-8B-A1B, one chip's part of four):
FFModel.compile -> FFModel.fit on seeded batches, as jobs/train.py does it
(whose fit call this loads), with what gated short convolutions, grouped
attention at 8,192 keys and a held share of sigmoid-routed experts add to
the set-up and the checks.

Set-up: build the model from the configuration's published keys and the
cell's flags, warm the step with one short fit, then the comparison with
the reference (benchmarks/lfm2_moe_reference.py, run over the first seeded
sequence's every position; at the positions where the k-th and (k+1)-th
of a router's scores + bias are a near-tie it takes the program's choice
of experts, as jobs/train_moe_lm.py does):
  (i)   the training graph's logits at positions [0, HEAD) and [seq -
        HEAD, seq): the far end is where a lost key block or a wrong
        group index shows;
  (ii)  `FFModel.eval`'s loss for the first batch against the reference's;
  (iii) the share of positions at which the program's choice was taken
        over the reference's own;
  (iv)  each grouped attention layer alone at the timed shape: the
        program's operator (the graph's own node: the repeat and the
        packed flash kernels at 8,192 keys) on the reference's input of
        that layer, its output at the far rows against the reference's,
        norm over norm. A stack's logits lose sight of its attention at
        a long context (a row there attends thousands of keys nearly
        alike: a shifted key head reads 1.09 of the largest logit at the
        near end and 0.08 at the far, PERF.md section 6, PR 60), so the
        far keys are held at the layer itself as well (1.4 there);
  (v)   a step's load of each held expert in each layer, printed: a cell
        in which one of them sees under a quarter of the even share is
        the seed's cell and is not `correct`.
Window: fit calls back to back, each over sequences of its own (made
before the window). After it: per expert layer the window's
assignments to held experts, to experts held elsewhere, those no expert
computed (0 in a dropless layer) and the last step's largest load over the
mean; every loss finite. A traced run also leaves the step program's
instructions by scope (lfm2_events.py; the expert layer's in
moe_events.py's form too, for the accepted readers).
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks import harness, lfm2_events, reference
from benchmarks import lfm2_moe_reference as ref
from benchmarks import traffic as traffic_gen

HEAD = 256          # positions compared at each end of the sequence

# What decides `correct` (PERF.md section 6, PR 60, has every reading each
# limit is set from: the program's over its seeds, and the controls').
LOGIT_TOL = 0.04
ATTN_FAR_TOL = 0.02
LOSS_TOL = 0.002
TIE_MARGIN = 0.06
MAX_TAKEN_SHARE = 0.25
MIN_HELD_LOAD = 0.25
train = harness.load_module("jobs", "train.py")


def lm_config(config: dict, sequence_length: int, attention_impl: str):
    """The program's TransformerLMConfig from the published keys
    (transformers' Lfm2MoeConfig naming)."""
    from flexflow_tpu.models import lfm2_moe_lm_config

    if sequence_length > config["max_position_embeddings"]:
        raise ValueError("the cell's sequences are longer than "
                         "max_position_embeddings")
    return lfm2_moe_lm_config(
        config, sequence_length=sequence_length,
        attention_impl=attention_impl,
        initializer_range=config["initializer_range"],
        embedding_range=config["embedding_initializer_range"])


def reference_model(config: dict) -> dict:
    return dict(
        layer_types=tuple(config["layer_types"]),
        num_dense_layers=config["num_dense_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        num_experts_per_tok=config["num_experts_per_tok"],
        eps=config["norm_eps"], rope_theta=float(config["rope_theta"]),
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        experts_held=tuple(config["experts_held"]))


def expert_layers(config: dict) -> list:
    return list(range(config["num_dense_layers"],
                      config["num_hidden_layers"]))


def moe_state(ff, config, key: str) -> list:
    return [ff._state[f"l{i}_moe"][key] for i in expert_layers(config)]


def step_text(ff, x, y, batch: int) -> str:
    """The compiled step's text: the step lowered and compiled again. The
    compile cache has the program by now, and set-up pays the lowering."""
    import jax

    data = ff._make_batch({k: v[:batch] for k, v in x.items()}, y[:batch])
    _, rng = jax.random.split(ff._rng)
    return ff.executor._train_step.lower(
        ff._params, ff._state, ff._opt_slots, ff._step, ff._counters, rng,
        data).compile().as_text()


def held_loads(ids, config, tokens: int) -> np.ndarray:
    """(expert layers, experts held) assignments of `tokens` tokens' choice
    `ids` (a list of (tokens, k) a layer) over the even share."""
    first, held = config["experts_held"]
    routed = config["experts_routed"]
    even = tokens * config["num_experts_per_tok"] / routed
    return np.stack([
        np.bincount(np.asarray(a).reshape(-1), minlength=routed)
        [first:first + held] / even for a in ids])


def attention_alone(ff, config, layer: int, n, positions):
    """The graph's own attention node `l<layer>_attn` run on `n` (1, seq,
    hidden) float32, the reference's input of that layer, in the compute
    dtype of the cell: the repeat of the KV heads and the packed flash
    kernels at the timed shape. Returns (1, seq, hidden) float32."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops.base import OpContext, get_op_def

    node = next(l for l in ff.layers if l.name == f"l{layer}_attn")
    dtype = ff.executor.compute_dtype or jnp.float32
    forward = get_op_def(node.op_type).forward

    @jax.jit
    def run(weights, x, pos):
        weights = jax.tree.map(lambda a: a.astype(dtype), weights)
        x = x.astype(dtype)
        (y,), _ = forward(node.params, [x, x, x, pos], weights, {},
                          OpContext(training=False, mesh=ff.mesh))
        return y.astype(jnp.float32)

    return run(ff._params[node.name], n, jnp.asarray(positions))


def check(ff, config, x, y, batch: int, spoil=None) -> dict:
    """The comparisons (i)-(v) of the module's docstring."""
    import jax
    import jax.numpy as jnp

    seq = x["tokens"].shape[1]
    n = min(HEAD, seq // 2)
    ends = np.r_[0:n, seq - n:seq]
    first = {k: v[:batch] for k, v in x.items()}
    ff.start_batch(first, np.zeros(first["tokens"].shape + (1,), np.int32))
    program = np.asarray(ff.forward()[0, ends], np.float32)
    ff._cached_logits = None    # the whole batch's logits; see jobs/train.py
    # a token's row in the op's (tokens, k) state is batch-major: sequence
    # j's positions are rows [j * seq, (j + 1) * seq)
    ids = [np.asarray(a) for a in moe_state(ff, config, "expert_ids")]
    loads = held_loads(ids, config, batch * seq)
    eval_loss = float(ff.eval(first, y[:batch], batch_size=batch)
                      .get_mean_loss())
    model = reference_model(config)

    def forward(params, tokens, positions, program_ids):
        mixers = {}
        logits, routing = ref.forward(
            params, tokens, positions, program_ids=program_ids,
            tie_margin=TIE_MARGIN, spoil=spoil, mixers=mixers, **model)
        keep = [{k: r[k] for k in ("tie", "own_ids", "biased")}
                for r in routing]
        return logits, keep, mixers

    forward = jax.jit(forward)
    ties = taken = differ = 0
    widest = attn_error = 0.0
    losses = []
    for j in range(batch):
        rows = slice(j * seq, (j + 1) * seq)
        tokens, positions = (x[k][j:j + 1] for k in ("tokens", "positions"))
        logits, routing, mixers = forward(
            ff._params, tokens, positions, [a[rows] for a in ids])
        labels = jnp.asarray(y[j].reshape(1, seq), jnp.int32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        losses.append(float(-jnp.mean(
            jnp.take_along_axis(logp, labels[..., None], axis=-1))))
        if j:
            continue
        theirs = np.asarray(logits[0])[ends]
        logit_error = reference.logit_error(program, theirs)
        # each end against the largest logit of both: where the error lies
        near, far = (float(np.max(np.abs(program[part] - theirs[part]))
                           / np.max(np.abs(theirs)))
                     for part in (slice(0, n), slice(n, None)))
        for layer, (n_in, out) in mixers.items():
            mine = np.asarray(attention_alone(ff, config, layer, n_in,
                                              positions))[0, seq - n:]
            theirs = np.asarray(out)[0, seq - n:]
            attn_error = max(attn_error, float(
                np.linalg.norm(mine - theirs) / np.linalg.norm(theirs)))
        for mine, r in zip(ids, routing):
            mine, k = mine[rows], mine.shape[1]
            tie = np.asarray(r["tie"])
            top = np.sort(np.asarray(r["biased"]), axis=1)[:, ::-1]
            gap = (top[:, k - 1] - top[:, k]) / top[:, k - 1]
            unlike = np.any(np.sort(mine, 1)
                            != np.sort(np.asarray(r["own_ids"]), 1), axis=1)
            ties += int(tie.sum())
            taken += int((tie & unlike).sum())
            differ += int(unlike.sum())
            if unlike.any():
                widest = max(widest, float(gap[unlike].max()))
    ref_loss = float(np.mean(losses))
    return {"compared_each_end": n, "logit_error": logit_error,
            "logit_error_near": near, "logit_error_far": far,
            "attn_far_error": attn_error,
            "eval_loss": eval_loss, "reference_loss": ref_loss,
            "loss_error": abs(eval_loss - ref_loss) / abs(ref_loss),
            "near_ties": ties, "choice_taken": taken,
            "compared": seq * len(ids), "routed_unlike": differ,
            "widest_swapped_gap": widest,
            "held_load_min": float(loads.min()),
            "held_load_max": float(loads.max()),
            "held_loads": [[round(float(v), 3) for v in row]
                           for row in loads]}


def passes(c: dict) -> dict:
    """{comparison: whether it holds} of a `check`."""
    return {
        "logits": c["logit_error"] <= LOGIT_TOL,
        "attention_far": c["attn_far_error"] <= ATTN_FAR_TOL,
        "loss": c["loss_error"] <= LOSS_TOL,
        "choice_taken": (c["choice_taken"] / c["compared"]
                         <= MAX_TAKEN_SHARE),
        "held_load": c["held_load_min"] >= MIN_HELD_LOAD}


def run(ctx, control=None) -> dict:
    t, cell, config = ctx.traffic, ctx.cell, ctx.config
    seq, batch = t["sequence_length"], t["global_batch"]
    layers = expert_layers(config)
    steps = t["trace_steps_per_call" if ctx.trace_dir else "steps_per_call"]
    cfg = lm_config(config, seq, cell["attention_impl"])
    with ctx.span("ffcompile"):
        ff = harness.build_lm(
            cfg, [*cell["flags"], "--seed", str(ctx.seed % (2**31 - 1))],
            batch, cell["optimizer"])
    # the rate of a warm-up's first steps (the cell file says why)
    ff.set_learning_rate(cell["learning_rate"])
    # a call's sequences are its own: a model of this size learns twelve
    # sequences of seeded tokens by heart in eight passes (the loss falls
    # from 9.7 to 0.2 inside one window and the routers move to the experts
    # held here, 28-45 % of the assignments where the draw gives 25: PERF.md
    # section 6, PR 60), and pre-training never sees a sequence twice. Made
    # before the window, enough for a step of 0.1 s a sequence; past that
    # the calls start over
    calls_made = int(ctx.seconds / (0.1 * steps * batch)) + 2
    made = [traffic_gen.train_batches(t, config["vocab_size"], ctx.seed + i,
                                      steps) for i in range(calls_made)]
    x, y = made[0]
    warm = t["warmup_steps"] * batch
    with ctx.span("warmup"):
        warm_loss, warm_s = train.fit_call(
            ff, {k: v[:warm] for k, v in x.items()}, y[:warm], batch)
    print(f"[train_lfm2] warm-up of {t['warmup_steps']} steps: {warm_s:.2f} "
          f"s, mean loss {warm_loss:.4f}")
    with ctx.span("reference_check"):
        c = check(ff, config, x, y, batch, spoil=control)
    ok = passes(c)
    print(f"[train_lfm2] held experts' loads over the even share, a row a "
          f"layer: {c['held_loads']} (least {c['held_load_min']:.3f}, at "
          f"least {MIN_HELD_LOAD})")
    n = c["compared_each_end"]
    print(f"[train_lfm2] against the reference over {seq} positions: logits "
          f"at [0, {n}) and [{seq - n}, {seq}) {c['logit_error']:.5f} "
          f"of max |logit| ({c['logit_error_near']:.5f} at the near end, "
          f"{c['logit_error_far']:.5f} at the far; tolerance {LOGIT_TOL}); "
          f"the grouped "
          f"attention layers alone at the far {n} rows "
          f"{c['attn_far_error']:.5f} norm over norm (tolerance "
          f"{ATTN_FAR_TOL}); eval loss {c['eval_loss']:.5f} against "
          f"{c['reference_loss']:.5f}, {c['loss_error']:.6f} apart "
          f"(tolerance {LOSS_TOL}); {c['near_ties']} of "
          f"{c['compared']} positions are near-ties of a router (margin "
          f"{TIE_MARGIN}), at {c['choice_taken']} of them the program's "
          f"choice was taken over the reference's (at most "
          f"{MAX_TAKEN_SHARE:.0%}); the program routed "
          f"{c['routed_unlike']} positions unlike the reference, the widest "
          f"gap it swapped across {c['widest_swapped_gap']:.5f}; holds: "
          f"{ok}")
    scoped = []
    if ctx.trace_dir:
        with ctx.span("scoped_instructions"):
            scoped = lfm2_events.scoped_instructions(
                step_text(ff, x, y, batch))

    def totals():
        return [np.asarray([int(a) for a in moe_state(ff, config, key)])
                for key in ("assignments_total", "dropped_total")]

    before = totals()
    calls = []
    t0 = ctx.open_window()
    while time.perf_counter() - t0 < ctx.seconds:
        with ctx.span("fit"):
            loss, dt = train.fit_call(ff, *made[len(calls) % len(made)],
                                      batch)
        calls.append((steps, dt, loss))
    ctx.close_window()

    done = sum(c_[0] for c_ in calls)
    bad = sum(c_[0] for c_ in calls if not math.isfinite(c_[2]))
    computed, dropped = (a - b for a, b in zip(totals(), before))
    held = computed + dropped
    chosen = done * batch * seq * config["num_experts_per_tok"]
    load = [float(a) for a in moe_state(ff, config, "load_max_over_mean")]
    # the held experts' loads in the window's last step, as in its first
    last = held_loads(moe_state(ff, config, "expert_ids"), config,
                      batch * seq)
    print(f"[train_lfm2] {len(calls)} fit calls of {steps} steps in "
          f"{ctx.window_s:.2f} s; mean losses "
          f"{[round(c_[2], 4) for c_ in calls]}; expert layers {layers}: "
          f"assignments to held experts {held.tolist()}, to experts held "
          f"elsewhere {(chosen - held).tolist()}, held and not computed "
          f"{dropped.tolist()}; last step: largest held expert's load "
          f"{[round(v, 3) for v in load]} x the mean, the held experts' "
          f"loads over the even share {last.min():.3f}-{last.max():.3f} "
          f"(before the window {c['held_load_min']:.3f}-"
          f"{c['held_load_max']:.3f})")
    return {
        "attempted": done, "failed": bad,
        # (the last step's loads too: a window over which the routers
        # moved their load off the held experts timed another cell)
        "correct": bool(all(ok.values()) and not dropped.any() and bad == 0
                        and math.isfinite(warm_loss)
                        and last.min() >= MIN_HELD_LOAD),
        "end_to_end": {"train_tok_s": done * batch * seq / ctx.window_s},
        "counters": {
            "steps": done, "tokens": done * batch * seq,
            "call_step_s": [c_[1] / c_[0] for c_ in calls],
            "assignments_held": held.tolist(),
            "assignments_elsewhere": (chosen - held).tolist(),
            "dropped_held": dropped.tolist(),
            "dropped_tokens": float(dropped.sum()),
            "load_max_over_mean": max(load),
            "held_share": float(held.sum() / (chosen * len(layers))),
            "held_load_last_min": float(last.min()),
            "held_load_last_max": float(last.max()),
            "mesh": {k: int(v) for k, v in ff.mesh.shape.items()},
            "lfm2_instructions": scoped,
            # the expert layer's pairs as moe_events.py reads them
            "moe_instructions": [[name, scope[4:]] for name, scope in scoped
                                 if scope.startswith("moe.")],
            **{k: v for k, v in c.items() if k != "held_loads"},
        },
    }
