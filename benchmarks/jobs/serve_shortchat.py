"""The short-chat serving job: FFModel inference compile -> serve() -> a
closed loop of clients, one a slot, each submitting its next request the
moment its last reply ended, on a hybrid graph (Jamba2-3B: per-slot
selective state-space state in 26 layers beside a paged pool for two
multi-query softmax layers), on the pattern of jobs/serve_reason.py, whose
replay, stream picking and bit test this job calls.

Set-up: build and compile the model as a user does, build the engine,
compare the decode graph's logits (a prompt prefilled in chunks through
the engine's own step program, then decoded rows) with the reference's
full forward, then run the loop until as many requests have ended as there
are clients and every prefill shape of the mix has run (the first round).
Window: the same loop, `engine.step()` after `engine.step()` in one
thread. After the window the loop is abandoned where it stands and the
window's batch is replayed through the decode graph (serve_reason.replay:
every slot gets a stream it served, in that slot, the prompt in the
engine's chunks through the engine's own step program and layout, then
the whole reply decoded in steps that only decode, each fed the token it
was served). Three of the streams (the longest prompt, the shortest that
ran in a slot another request had left, the longest context) have every
decoded row's logits and the h and the convolution's tail their slots end
with held to the reference's full forward over prompt and reply
(benchmarks/jamba2_reference.py); of every replayed stream, the share of
its served tokens that are the replay's own argmax is held to SAME_SHARE:
tokens served from a state that leaked between slots, or was not reset for
a new request, are not the replay's.

`correct` needs: the state update alone (the program's kernel at the
engine's own state leaf's shape) within STATE_TOL, the slots' h after the
window and after the replay float32 in fact (STATE_F32_SHARE), the check
and the three compared streams within LOGIT_TOL, STATE_END_TOL and
TAIL_TOL, every replayed stream's share over SAME_SHARE, every request
that ended in the window of the asked length with ids of the vocabulary,
and (the harness adds) nothing compiled inside the window.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks import harness
from benchmarks import jamba2_reference as reference
from benchmarks import traffic as traffic_gen

# `replay`, `pick_streams`, `lower_logits_step`, `f32_share`
reason = harness.load_module("jobs", "serve_reason.py")
CHECK_STREAMS = reason.CHECK_STREAMS
# The limits, each between the sound program's largest reading over
# twenty-two runs (thirteen at a prefill chunk of 1,024, nine at the
# cell's 512, where the check's prompt of 600 rides in two chunks) and the
# smallest reading of a spoiled control (the reference spoiled: e4m3
# matrices, a bf16 h, the three inner norms, the convolution's bias, D or
# dt's bias left out; the program spoiled: a bf16 h, no reset, states
# swapped between slots): scripts/jamba2_controls.py makes the table,
# PERF.md section 6 (PR 55) has it. Sound | controls (a run's largest):
# LOGIT_TOL 0.027-0.042 | 0.16-1.5 (no reset 0.26-0.82 over three seeds);
# STATE_TOL 0 | 4.3e-3, 6.1e-3; STATE_F32_SHARE 0.9999 | 0; STATE_END_TOL
# 0.027-0.080 | 0.17-9.2 (e4m3 1.76 at the least, no reset 0.74-0.91);
# TAIL_TOL 0.027-0.045 | 0.15-1.7; SAME_SHARE 0.828-0.875 | 0.13
# (swapped), 0.63-0.67 (no reset, three seeds, which the logits and the
# last h catch too). LOGIT_TOL: max |logit difference| over max |reference
# logit|, prefill through the cache then decoded rows, bf16 program
# against the float32 reference. STATE_TOL: the
# state update alone, the program's kernel a token a call against the
# reference's scan on the same operands (bf16 compute noise hides a bf16 h
# from the logits; this check has no such noise). STATE_F32_SHARE: the
# least share of a slot's h entries, read from the engine's own leaf,
# whose float32 value is no bfloat16. STATE_END_TOL, TAIL_TOL: a compared
# slot's last h and last convolution inputs against the reference's after
# the same tokens, max |difference| over max |entry| (0.15 for the last h
# since the runs at 512: the check's slot, 608 tokens over a chunk's
# boundary, read 0.033-0.080 over eleven seeds where one chunk read
# 0.030-0.049, and 0.1 left a quarter of room over that). SAME_SHARE: the
# least share of a replayed stream's served tokens that are the replay's
# argmax.
LOGIT_TOL = 0.1
STATE_TOL = 3e-5
STATE_TOKENS = 16
STATE_F32_SHARE = 0.5
STATE_END_TOL = 0.15
TAIL_TOL = 0.1
SAME_SHARE = 0.75


def build_model(ctx):
    """The compiled model, from the flags a user would put on the command
    line: the trunk builder, an inference compile."""
    from flexflow_tpu import (
        FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
    )
    from flexflow_tpu.fftype import CompMode
    from flexflow_tpu.models import build_transformer_lm, jamba_lm_config

    cell = ctx.cell
    cfg = jamba_lm_config(
        ctx.config, sequence_length=cell["train_sequence_length"],
        attention_impl=cell["attention_impl"],
        initializer_range=ctx.config["initializer_range"])
    argv = sys.argv
    sys.argv = [argv[0], "-b", str(cell["train_batch"]), *cell["flags"],
                "--seed", str(ctx.seed % (2**31 - 1))]
    try:
        config = FFConfig()
    finally:
        sys.argv = argv
    ff = FFModel(config)
    build_transformer_lm(ff, cfg, batch_size=cell["train_batch"])
    ff.compile(
        optimizer=SGDOptimizer(),
        loss_type=LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
        metrics=[MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY],
        comp_mode=CompMode.COMP_MODE_INFERENCE)
    return ff


def ssm_nodes(engine) -> list:
    """The decode graph's state-space layers, in order."""
    dec = engine.decode_model
    return [n.name for n in dec.graph.topo_order()
            if "state_h" in dec._state.get(n.name, {})]


def logits_step(engine):
    """The engine's own graph as a step that hands back, of a call that
    only decodes, the logits rows of the slots `picked` names and every
    slot's argmax, in the shape serve_reason.replay takes a step in (this
    graph routes nothing: its third result is empty)."""
    import jax
    import jax.numpy as jnp

    ex, slots = engine.decode_model.executor, engine.spec.slots

    def step_logits(params, state, xs, picked):
        logits, new_state, _ = ex._apply(
            params, state, ex._cast_compute(xs), training=False, rng=None)
        logits = logits[:slots, 0].astype(jnp.float32)
        return (ex._pin_at_rest(ex._restore_state_dtypes(new_state)),
                logits[picked], jnp.zeros((0, picked.shape[0], 1), jnp.int32),
                jnp.argmax(logits, axis=-1).astype(jnp.int32))

    return jax.jit(step_logits, donate_argnums=(1,))


def slot_states(engine, slots) -> dict:
    """{slot: (the state-space layers' h (N, E) of that slot, their
    convolution's tails (K - 1, E)), in the layers' order}, read from the
    engine's own leaves."""
    engine._complete_in_flight()
    dec = engine.decode_model
    leaves = [dec._state[name] for name in ssm_nodes(engine)]
    if any(str(leaf["state_h"].dtype) != "float32" for leaf in leaves):
        raise TypeError("the state-space layers' h leaf is not float32")
    return {s: ([np.asarray(leaf["state_h"][s]) for leaf in leaves],
                [np.asarray(leaf["state_conv"][s].astype("float32"))
                 for leaf in leaves]) for s in slots}


def state_check(ctx, engine, state_dtype=None) -> float:
    """The selective scan's state update by itself, at the shape of the
    engine's own state leaf (a row a slot): STATE_TOKENS tokens a row at
    the configuration's channels, seeded operands in the ranges the layer
    gives them (dt = softplus of the published bias range, B and C of unit
    mean square as the inner norms leave them, A = -(1 .. N), D ones), run
    by the program's own function a token a call with the state carried,
    as a decode step runs it (kernels/selective_scan.selective_scan_update:
    the Pallas kernel on a chip), against the reference's scan. The largest
    error of the outputs and of the last state, as a share of their
    largest. `state_dtype`: what the reference keeps h in (float32;
    bfloat16 is the control, which has to come out over STATE_TOL)."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels.selective_scan import selective_scan_update

    leaf = engine.decode_model._state[ssm_nodes(engine)[0]]["state_h"]
    rows, N, E = leaf.shape
    rng = np.random.default_rng(ctx.seed)

    def normal(*shape):
        return rng.standard_normal(shape, np.float32)

    dt = np.log1p(np.exp(rng.uniform(-6.9, -2.25, (E,))
                         + 0.3 * normal(rows, STATE_TOKENS, E)))
    c = normal(rows, STATE_TOKENS, E)
    B, C = normal(rows, STATE_TOKENS, N), normal(rows, STATE_TOKENS, N)
    A = -np.broadcast_to(np.arange(1, N + 1, dtype=np.float32)[:, None],
                         (N, E))
    dt, c, B, C, A = (jnp.asarray(a, jnp.float32) for a in (dt, c, B, C, A))
    D = jnp.ones((E,), jnp.float32)
    update = jax.jit(selective_scan_update, donate_argnums=(0,))
    live, keep = jnp.ones((rows, 1), bool), jnp.ones((rows,), bool)
    state, outs = jnp.zeros_like(leaf), []
    for t in range(STATE_TOKENS):
        at = slice(t, t + 1)
        y, state = update(state, dt[:, at], c[:, at], B[:, at], C[:, at], A,
                          D, live, keep)
        outs.append(y)
    with jax.default_matmul_precision("highest"):
        want_y, want_h = jax.jit(lambda *a: jax.lax.map(
            lambda row: reference.ssm_recurrence(
                *row, A.T, D, state_dtype=state_dtype or jnp.float32),
            a, batch_size=16))(dt, c, B, C)
    return max(reference.logit_error(jnp.concatenate(outs, 1), want_y),
               reference.logit_error(jnp.swapaxes(state, 1, 2), want_h))


def compare(engine, ctx, replayed, states, pad_to: int, pad_rows: int,
            spoil=None) -> dict:
    """A replayed stream against the reference's full forward over its
    tokens, padded to `pad_to` (causal: the tail is unseen; one length and
    `pad_rows` compared rows, one set of programs): the largest logit
    error of its decoded rows, and its slot's last h and convolution tail
    (`states`: the layers', from the engine's leaves) against the
    reference's after the last token fed."""
    tokens, at, rows, _ = replayed
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(tokens)] = tokens
    rows_at = np.zeros((max(pad_rows, len(at)),), np.int32)
    rows_at[:len(at)] = at
    want, report = reference.forward(
        harness.param_getter(engine.decode_model), padded, ctx.config,
        rows=rows_at, spoil=spoil, state_at=len(tokens) - 1)
    h, tails = states
    return {"error": reference.logit_error(rows, want[:len(at)]),
            "rows": len(at),
            "state_error": max(map(reference.logit_error, h,
                                   report["states"])),
            "tail_error": max(map(reference.logit_error, tails,
                                  report["tails"])),
            "state_f32": min(map(reason.f32_share, h))}


def sound(r: dict) -> bool:
    return bool(r["error"] <= LOGIT_TOL and r["state_error"] <= STATE_END_TOL
                and r["tail_error"] <= TAIL_TOL
                and r["state_f32"] >= STATE_F32_SHARE)


def said(r: dict) -> str:
    return (f"{r['error']:.5f} of max |logit| over {r['rows']} rows "
            f"(tolerance {LOGIT_TOL}); the slot's last h "
            f"{r['state_error']:.5f} of its largest entry (tolerance "
            f"{STATE_END_TOL}), {100 * r['state_f32']:.2f} % of it no "
            f"bfloat16; its convolution's tail {r['tail_error']:.5f} "
            f"(tolerance {TAIL_TOL})")


def scoped_instructions(lowered: dict) -> dict:
    """The `[bucket, instruction name, scope]` triples of the engine's
    step programs for the per-layer readers of a traced run, from the text
    of each program compiled ahead (`lowered`: {chunk bucket, 0 for the
    step that only decodes: its lowering, which keeps its executable})."""
    from benchmarks import jamba2_events

    return {"jamba2_instructions": [
        triple for bucket, step in lowered.items()
        for triple in jamba2_events.scoped_instructions(
            step.compile().as_text(), bucket)]}


def run(ctx) -> dict:
    serve = harness.load_module("jobs", "serve.py")  # the latency arithmetic
    # `Ahead` and `lower_step`: programs compiled ahead, in threads
    sessions = harness.load_module("jobs", "serve_sessions.py")
    t, cell = ctx.traffic, ctx.cell
    vocab = ctx.config["vocab_size"]
    with ctx.span("ffcompile"):
        ff = build_model(ctx)
    with ctx.span("ffcompile"):
        engine = ff.serve(**cell["serve"])
    mgr, slots = engine.block_manager, engine.spec.slots
    print(f"[shortchat] engine: {slots} slots x {engine.max_seq_len}, "
          f"prefill chunk {engine.spec.prefill_chunk}, pool "
          f"{mgr.num_blocks} blocks of {mgr.block_size}, chunks as "
          f"{'rows' if engine._chunk_rows else 'a rectangle'}; "
          f"{engine.stats()['state_bytes'] / 1e9:.2f} GB of slot state in "
          f"{len(ssm_nodes(engine))} state-space layers")

    def padded(n: int) -> int:  # the lengths the reference compiles for
        return n + -n % 256

    def replay(streams, picked):
        got = reason.replay(engine, step, streams, picked)
        # serve_reason's reads the delta rule's leaves: this graph's
        got["states"] = slot_states(engine, picked)
        return got

    rng = np.random.default_rng(ctx.seed)
    step = logits_step(engine)
    sizes = traffic_gen.request_sizes(t)
    # every compared sequence is padded to one length and one count of
    # rows, so that the reference's programs are compiled once
    pad_to, pad_rows = padded(max(sizes[0]) + max(sizes[1])), max(sizes[1])
    chunk = engine.spec.prefill_chunk
    ahead = sessions.Ahead()
    # the buckets of the check's chunks, in order
    first = [engine._bucket(min(chunk, n - at))
             for n in t["check_prompt_tokens"] for at in range(0, n, chunk)]
    lowered = {}  # {bucket, 0 for a step that only decodes: the step's}

    def lower(b: int):
        lowered[b] = sessions.lower_step(engine, engine._step_fn, b, False)
        ahead.add(f"engine@{b}", lowered[b])

    if engine._chunk_rows:
        with ctx.span("lower_ahead"):
            # every program of the run, lowered at the shapes the loop
            # calls it with (`lower_step`: a chunk as rows) and compiled
            # in threads beside the check, in the order of need: the
            # check's chunk steps and its logits step, the step that only
            # decodes, the mix's other chunks
            rest = {engine._bucket(n % chunk or chunk) for n in sizes[0]}
            for b in dict.fromkeys([*first, *sorted(rest, reverse=True)]):
                lower(b)
                if b == first[-1]:
                    ahead.add("check@0",
                              reason.lower_logits_step(engine, step))
                    lower(0)
    with ctx.span("reference_check"):
        state_error = state_check(ctx, engine)
        print(f"[shortchat] the state update alone, {slots} rows of "
              f"{STATE_TOKENS} tokens a call at a time against the "
              f"reference's scan: {state_error:.2e} of the largest "
              f"(tolerance {STATE_TOL})")
        if ahead.pending:
            ahead.wait("check@0", *(f"engine@{b}" for b in first))
        prompts = {i: (rng.integers(0, vocab, n).tolist(), None)
                   for i, n in enumerate(t["check_prompt_tokens"])}
        got = replay(prompts, list(prompts))
        check = [compare(engine, ctx, got["streams"][i], got["states"][i],
                         pad_to, pad_rows) for i in prompts]
    for r in check:
        print(f"[shortchat] decode-graph logits, chunked prefill + "
              f"{reason.CHECK_DECODED} decoded: {said(r)}")

    stream = traffic_gen.requests(t, vocab, ctx.seed)
    asked, client_of, slot_of, steps, finished = {}, {}, {}, [], []
    live = []

    def submit(client: int):
        prompt, new = next(stream)
        with ctx.span("submit"):
            req = engine.submit(prompt, max_new_tokens=new)
        asked[req.request_id], client_of[req.request_id] = new, client

    def pump():
        before = engine._prefill_calls
        t0 = time.perf_counter()
        with ctx.span("engine_step"):
            done = engine.step()
        steps.append((t0, time.perf_counter(),
                      engine._prefill_calls > before))
        active = engine.scheduler.active_slots
        live.append(len(active))
        for s in active:
            slot_of.setdefault(s.request.request_id, s.index)
        for req in done:
            finished.append(req)
            submit(client_of[req.request_id])
        return done

    with ctx.span("compile_wait"):
        ahead.wait()  # nothing compiles beside the window
        ahead.pool.shutdown()
    scoped = {}
    if ctx.trace_dir:
        with ctx.span("scoped_instructions"):
            if not lowered:  # chunks as a rectangle: nothing was ahead
                lowered[0] = sessions.lower_step(
                    engine, engine._step_fn, 0, False)
            scoped = scoped_instructions(lowered)
    with ctx.span("first_round"):
        # until as many requests have ended as there are clients (four
        # cycles of the mix's sizes) and every prefill shape has run
        for c in range(t["clients"]):
            submit(c)
        shapes = {engine._bucket(n % chunk or chunk) for n in sizes[0]}
        while len(finished) < t["clients"] or shapes - {
                engine._bucket(len(r.prompt) % chunk or chunk)
                for r in finished}:
            pump()

    before = engine.stats()
    w0 = ctx.open_window()
    first_step = len(steps)
    while time.perf_counter() - w0 < ctx.seconds:
        pump()
    w1 = ctx.close_window()
    after = engine.stats()
    tokens = after["decode_tokens"] - before["decode_tokens"]

    def grew(key):
        return after[key] - before[key]

    def whole(r):
        return (r.finished and len(r.generated) == asked[r.request_id]
                and all(0 <= tok < vocab for tok in r.generated))

    ended = [r for r in finished if w0 <= r.finish_t <= w1]
    wrong = [r for r in ended if not whole(r)]
    right = [r for r in ended if r not in wrong]
    in_window = [(a, b, pre) for a, b, pre in steps if a >= w0 and b <= w1]
    step_ms = sorted(1e3 * (b - a) for a, b, _ in in_window) or [0.0]
    prefill_step_s = [b - a for a, b, pre in in_window if pre]
    live_slots = float(np.mean(live[first_step:] or [0]))
    # the state the timed program left, from the engine's own leaves
    window_f32 = min(reason.f32_share(layer) for h, _ in slot_states(
        engine, range(min(slots, CHECK_STREAMS))).values() for layer in h)
    print(f"[shortchat] {len(ended)} requests ended in {ctx.window_s:.2f} "
          f"s ({len(wrong)} wrong), {tokens} tokens, {len(in_window)} "
          f"engine steps, {len(prefill_step_s)} of them with a prefill "
          f"chunk, {live_slots:.1f} slots live and "
          f"{tokens / max(len(in_window), 1):.1f} rows decoding a step; an "
          f"engine step: "
          f"median {step_ms[len(step_ms) // 2]:.2f} ms, 90th percentile "
          f"{step_ms[len(step_ms) * 9 // 10]:.2f}; "
          f"{grew('state_resets')} slots reset for a new request; "
          f"{100 * window_f32:.2f} % of the slots' h is no bfloat16")

    with ctx.span("stream_replay"):
        # the window's batch (of all that ended, where a short, traced
        # window saw none end): a stream a slot, every slot live
        pool = right or [r for r in finished if whole(r)]
        by_slot, picked, reused = reason.pick_streams(
            pool, [r for r in finished if whole(r)], slot_of)
        got = replay({s: (r.prompt, r.generated) for s, r in by_slot.items()},
                     picked)
    with ctx.span("stream_check"):
        results = []
        for s in picked:
            r = by_slot[s]
            results.append(compare(engine, ctx, got["streams"][s],
                                   got["states"][s], pad_to, pad_rows))
            print(f"[shortchat] served stream of {len(r.prompt)} + "
                  f"{len(r.generated)} tokens in slot {s}, replayed beside "
                  f"{len(got['same']) - 1} others ({got['same'][s][0]} of "
                  f"its tokens are the replay's own argmax): "
                  f"{said(results[-1])}")
    shares = {s: same / n for s, (same, n) in got["same"].items()}
    same_share = (sum(same for same, _ in got["same"].values())
                  / sum(n for _, n in got["same"].values()))
    apart = [s for s, share in shares.items() if share < SAME_SHARE]
    print(f"[shortchat] {len(shares)} served streams replayed, every slot "
          f"live: {100 * same_share:.2f} % of their "
          f"{sum(n for _, n in got['same'].values())} tokens are the "
          f"replay's argmax, {100 * min(shares.values()):.2f} % of the "
          f"stream that agrees least (the least allowed: "
          f"{100 * SAME_SHARE:.0f} %); {reused} of the streams that ended "
          f"ran in a reused slot")
    off = [r for r in results if not sound(r)]
    ttft, tpot = serve.request_latencies(right)
    latencies = serve.latency_statistics(ttft, tpot)
    phases = ("ffcompile", "lower_ahead", "reference_check", "compile_wait",
              "first_round", "stream_replay", "stream_check")
    print("[shortchat] seconds beside the window: " + ", ".join(
        f"{name} {sum(ctx.seconds_in(name)):.1f}" for name in phases))
    return {
        "attempted": len(ended),
        "failed": len(wrong) + len(off) + len(apart),
        "correct": bool(
            state_error <= STATE_TOL and all(map(sound, check))
            and window_f32 >= STATE_F32_SHARE
            and results and not off and not apart and not wrong and ended),
        "end_to_end": {"serve_tok_s": tokens / ctx.window_s, **latencies},
        "counters": {
            "tokens": tokens, "requests": len(ended),
            "step_s": [b - a for a, b, _ in in_window],
            "prefill_step_s": prefill_step_s,
            "logit_error": max(r["error"] for r in check),
            "state_error": state_error,
            "stream_logit_error": max(
                (r["error"] for r in results), default=None),
            "stream_state_error": max(
                (r["state_error"] for r in results), default=None),
            "stream_tail_error": max(
                (r["tail_error"] for r in results), default=None),
            "state_f32_share": min(
                [window_f32, *(r["state_f32"] for r in results)]),
            "replayed_streams": len(shares),
            "same_share": same_share,
            "same_share_min": min(shares.values()),
            "live_slots": live_slots,
            "decoding_rows": tokens / max(len(in_window), 1),
            "chunk_steps_pct": 100.0 * len(prefill_step_s)
            / max(len(in_window), 1),
            "prefill_share_pct": 100.0 * sum(prefill_step_s) / ctx.window_s,
            "state_resets": grew("state_resets"),
            **scoped,
            **{k: round(v, 3) for k, v in latencies.items()
               if v is not None},
        },
    }
