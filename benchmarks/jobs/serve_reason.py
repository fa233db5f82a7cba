"""The reasoning-traffic serving job: FFModel inference compile -> serve()
-> a closed loop of clients, one a slot, each submitting its next request
the moment its last reply ended, on a graph with recurrent layers
(Solar-Open2: per-slot delta-rule state beside the paged pool).

Set-up: build and compile the model as a user does, build the engine,
compare the decode graph's logits (a prompt prefilled in chunks through
the engine's own step program, then decoded rows) with the reference's
full forward, then run the loop until as many requests have ended as there
are clients and every prefill shape of the mix has run (the first round:
not until every client has its first reply back, which is 2,048 steps and
more and makes a cold run outlast the driver's limit). Window: the
same loop, `engine.step()` after `engine.step()` in one thread. After the
window the loop is abandoned where it stands (a reply is up to 2,048
steps: draining it would outlast the window) and the window's batch is
replayed through the decode graph: every slot gets a stream it served
(one that ended inside the window where it has one), in that slot, the
prompt in the engine's chunks through the engine's own step program and
layout, then the whole reply decoded in steps that only decode with every
slot live, each fed the token it was served whatever the replay would
sample (the two programs part at bf16 near-ties). Three of the streams
(the longest prompt, the shortest that ran in a slot another request had
left, the longest context) have every decoded row's logits, their experts and the
delta-rule state their slots end with held to the reference's full
forward over prompt and reply, which tests every expert the program chose
(benchmarks/solar_open2_reference.py); of every replayed stream, the share
of its served tokens that are the replay's own argmax is held to
SAME_SHARE: tokens served from a state that leaked between slots, or
was not reset for a new request, are not the replay's.

`correct` needs: the state update alone (the program's kernel at the
engine's own state leaf's shape) within STATE_TOL, the slots' state after
the window and after the replay float32 in fact (STATE_F32_SHARE), the
check and the three compared streams within LOGIT_TOL, ROUTE_BAD_SHARE
and STATE_END_TOL, every replayed stream's share over SAME_SHARE, every
request that ended in the window of the asked length with ids of the
vocabulary, no assignment to a held expert dropped, and (the harness
adds) nothing compiled inside the window.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from benchmarks import harness
from benchmarks import solar_open2_reference as reference
from benchmarks import traffic as traffic_gen

CHECK_DECODED = 8
CHECK_STREAMS = 3
# The limits, each between the sound program's largest reading and a
# control's (the reference spoiled: e4m3 weights, a bf16 state, no
# convolution, beta without its factor 2; the program spoiled: a bf16
# state, no reset, states swapped between slots): PERF.md section 6 (PR
# 33) has the table. LOGIT_TOL: max |logit difference| over max |reference
# logit|, prefill through the cache then decoded rows, bf16 program
# against the float32 reference. ROUTE_MARGIN: how far under the
# reference's k-th probability (as a share of it) an expert of the
# program's may lie; ROUTE_BAD_SHARE: the share of compared routings that
# may lie further. STATE_TOL: the state update alone, the program's kernel
# a token a call against the reference's scan on the same operands (bf16
# compute noise hides a bf16 state from the logits: both move them by some
# 0.02-0.05; this check has no such noise). STATE_F32_SHARE: the least
# share of a slot's state entries, read from the engine's own leaf, whose
# float32 value is no bfloat16 (a state kept in or passed through bfloat16
# has none). STATE_END_TOL: a compared slot's last state against the
# reference's after the same tokens, max |difference| over max |entry|.
# SAME_SHARE: the least share of a replayed stream's served tokens that
# are the replay's argmax.
LOGIT_TOL = 0.1
ROUTE_MARGIN = 0.2
ROUTE_BAD_SHARE = 0.005
STATE_TOL = 3e-4
STATE_TOKENS = 16
STATE_F32_SHARE = 0.5
STATE_END_TOL = 0.15
SAME_SHARE = 0.88


def build_model(ctx):
    """The compiled model, from the flags a user would put on the command
    line: the trunk builder, an inference compile."""
    from flexflow_tpu import (
        FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
    )
    from flexflow_tpu.fftype import CompMode
    from flexflow_tpu.models import (
        build_transformer_lm, solar_open2_lm_config,
    )

    cell = ctx.cell
    cfg = solar_open2_lm_config(
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


def delta_nodes(engine) -> list:
    """The decode graph's delta-rule layers, in order."""
    dec = engine.decode_model
    return [n.name for n in dec.graph.topo_order()
            if "state_s" in dec._state.get(n.name, {})]


def logits_step(engine, n: int):
    """The engine's own graph as a step that hands back, of a call that
    only decodes, the logits rows of the `n` slots `picked` names and the
    experts every expert layer chose for them, and every slot's argmax:
    (state, (n, vocab) float32, (layers, n, k) int32, (slots,) int32)."""
    import jax
    import jax.numpy as jnp

    ex, moe = engine.decode_model.executor, engine._moe_nodes
    slots = engine.spec.slots

    def step_logits(params, state, xs, picked):
        logits, new_state, _ = ex._apply(
            params, state, ex._cast_compute(xs), training=False, rng=None)
        logits = logits[:slots, 0].astype(jnp.float32)
        ids = jnp.stack([new_state[name]["expert_ids"][picked]
                         for name in moe])
        return (ex._pin_at_rest(ex._restore_state_dtypes(new_state)),
                logits[picked], ids,
                jnp.argmax(logits, axis=-1).astype(jnp.int32))

    return jax.jit(step_logits, donate_argnums=(1,))


def lower_logits_step(engine, step):
    """`step` lowered at the shapes `replay` calls it with, for `Ahead`."""
    import jax.numpy as jnp

    dec, slots = engine.decode_model, engine.spec.slots
    xs = engine._stage_inputs(
        np.zeros((slots, 1), np.int32),
        np.full((slots, 1), engine.max_seq_len, np.int32))
    return step.lower(dec._params, dec._state, xs,
                      jnp.zeros((CHECK_STREAMS,), jnp.int32))


def f32_share(state) -> float:
    """The share of float32 entries that no bfloat16 holds (their low 16
    bits are not all zero)."""
    bits = np.ascontiguousarray(state, np.float32).view(np.uint32)
    return float(np.mean((bits & 0xFFFF) != 0))


def slot_states(engine, slots) -> dict:
    """{slot: the delta-rule layers' state (H, d, d) of that slot, in the
    layers' order}, read from the engine's own leaves."""
    engine._complete_in_flight()
    dec = engine.decode_model
    leaves = [dec._state[name]["state_s"] for name in delta_nodes(engine)]
    if any(str(leaf.dtype) != "float32" for leaf in leaves):
        raise TypeError("the delta rule's state leaf is not float32")
    return {s: [np.asarray(leaf[s]) for leaf in leaves] for s in slots}


def state_check(ctx, engine, state_dtype=None) -> float:
    """The delta rule's state update by itself, at the shape of the
    engine's own state leaf (a row a slot): STATE_TOKENS tokens a row at
    the configuration's heads, seeded operands in the ranges the layer
    gives them (l2-normalised q and k, alpha from the published decay
    ranges, beta in (0, 2)), run by the program's own function a token a
    call with the state carried, as a decode step runs it
    (kernels/delta_rule.delta_rule_update: the Pallas kernel on a chip),
    against the reference's scan. The largest error of the outputs and of
    the last state, as a share of their largest. `state_dtype`: what the
    reference keeps its state in (float32; bfloat16 is the control, which
    has to come out over STATE_TOL)."""
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.kernels.delta_rule import delta_rule_update

    leaf = engine.decode_model._state[delta_nodes(engine)[0]]["state_s"]
    rows, H, d, _ = leaf.shape
    rng = np.random.default_rng(ctx.seed)
    shape = (rows, STATE_TOKENS, H, d)

    def unit(a):
        return a / np.linalg.norm(a, axis=-1, keepdims=True)

    def normal(shape):
        return rng.standard_normal(shape, np.float32)

    q, k = unit(normal(shape)) * d ** -0.5, unit(normal(shape))
    v = normal(shape)
    step_size = np.log1p(np.exp(rng.uniform(-6.9, -2.25, (H, d))
                                + 0.3 * normal(shape)))
    alpha = np.exp(-np.exp(rng.uniform(0.0, 2.77, (H, 1))) * step_size)
    beta = rng.uniform(0.0, 2.0, shape[:3])
    q, k, v, alpha, beta = (jnp.asarray(a, jnp.float32)
                            for a in (q, k, v, alpha, beta))
    update = jax.jit(delta_rule_update, donate_argnums=(0,))
    live, keep = jnp.ones((rows, 1), bool), jnp.ones((rows,), bool)
    state, outs = jnp.zeros_like(leaf), []
    for t in range(STATE_TOKENS):
        at = slice(t, t + 1)
        o, state = update(state, q[:, at], k[:, at], v[:, at], alpha[:, at],
                          beta[:, at], live, keep)
        outs.append(o)
    with jax.default_matmul_precision("highest"):
        # 16 rows at a time: a row's state is 4 MB a head block, twice
        want_o, want_s = jax.jit(lambda *a: jax.lax.map(
            lambda row: reference.delta_recurrence(
                *row, state_dtype=state_dtype or jnp.float32),
            a, batch_size=16))(q, k, v, alpha, beta)
    return max(reference.logit_error(jnp.concatenate(outs, 1), want_o),
               reference.logit_error(state, want_s))


def replay(engine, step, streams: dict, picked: list) -> dict:
    """`streams` = {slot: (prompt, reply or None)} through the decode
    graph, each in its slot with blocks of its own: the prompts one after
    another in the engine's chunks, each chunk through the engine's own
    step program in the layout the engine gives a chunk step (rows past
    the slots where the paged kernel serves them, else the rectangle),
    then one call of `step` a reply token with every stream live until its
    reply ends, fed the served token (`reply` None: CHECK_DECODED tokens
    of the replay's own argmax). A stream the pool has no blocks left for
    (the `picked` slots' come first) is left out. Returns

      streams: {picked slot: (tokens fed, positions of the decoded rows,
        their logits, the experts chosen there (layers, rows, k))};
      same: {slot: (how many of its tokens are the replay's argmax, of how
        many)}, the first token (a chunk step's sample) among them;
      states: `slot_states` of the picked slots after the last step.

    The first reply token is sampled from a chunk step's row, whose logits
    the engine's program does not hand back: the compared rows start at
    the second. Where every stream has its reply, a step is dispatched
    before the one before it is fetched."""
    import jax
    import jax.numpy as jnp

    engine._complete_in_flight()
    dec, slots = engine.decode_model, engine.spec.slots
    mgr, dead = engine.block_manager, engine.max_seq_len
    chunk = engine.spec.prefill_chunk
    table = np.zeros((slots, mgr.table_width), np.int32)
    want, free = {}, 1                      # block 0 is the scratch block
    for s in [*picked, *(s for s in streams if s not in picked)]:
        prompt, reply = streams[s]
        n = len(reply) if reply else 1 + CHECK_DECODED
        need = -(-(len(prompt) + n - 1) // mgr.block_size)
        if free + need > mgr.num_blocks:
            if s in picked:
                raise ValueError("pool too small for the compared streams")
            continue
        table[s, :need] = free + np.arange(need)
        want[s], free = n, free + need

    def staged(tokens, positions, row_slots=None):
        xs = engine._stage_inputs(tokens, positions, row_slots)
        xs["page_table"] = jax.device_put(
            table if row_slots is None else table[row_slots],
            xs["page_table"].sharding)
        return xs

    firsts = {}
    for s in want:
        prompt = streams[s][0]
        for at in range(0, len(prompt), chunk):
            piece = prompt[at:at + chunk]
            n, b = len(piece), engine._bucket(len(piece))
            if engine._chunk_rows:
                tokens = np.zeros((slots + b, 1), np.int32)
                positions = np.full((slots + b, 1), dead, np.int32)
                tokens[slots:slots + n, 0] = piece
                positions[slots:slots + n, 0] = np.arange(at, at + n)
                row_slots = np.r_[np.arange(slots), np.full((b,), s)]
                last = slots + n - 1
            else:
                tokens = np.zeros((slots, b), np.int32)
                positions = np.full((slots, b), dead, np.int32)
                tokens[s, :n], positions[s, :n] = piece, np.arange(at, at + n)
                row_slots, last = None, s
            read_idx = np.zeros((tokens.shape[0],), np.int32)
            if row_slots is None:
                read_idx[s] = n - 1
            dec._state, sampled = engine._step_fn(
                dec._params, dec._state, staged(tokens, positions, row_slots),
                jnp.asarray(read_idx), jax.random.key(0),
                jnp.zeros((tokens.shape[0],), jnp.float32))
        firsts[s] = int(np.asarray(sampled)[last])

    # what a stream is fed: its reply, or its first sample and then the
    # replay's own argmaxes
    fed = {s: list(streams[s][1] or [firsts[s]]) for s in want}
    own = any(streams[s][1] is None for s in want)
    same = {s: int(fed[s][0] == firsts[s]) for s in want}
    rows = {s: [] for s in picked}
    experts = {s: [] for s in picked}
    pick = jnp.asarray((list(picked) * CHECK_STREAMS)[:CHECK_STREAMS],
                       jnp.int32)

    def settle(t, live, logits, ids, top):
        logits, ids, top = np.asarray(logits), np.asarray(ids), np.asarray(top)
        for j, s in enumerate(picked):
            if s in live:
                rows[s].append(logits[j])
                experts[s].append(ids[:, j])
        for s in live:
            if streams[s][1] is None:
                fed[s].append(int(top[s]))
            same[s] += fed[s][t + 1] == top[s]

    pending = None
    for t in range(max(want.values()) - 1):
        tokens = np.zeros((slots, 1), np.int32)
        positions = np.full((slots, 1), dead, np.int32)
        live = [s for s in want if t + 1 < want[s]]
        for s in live:
            tokens[s, 0] = fed[s][t]
            positions[s, 0] = len(streams[s][0]) + t
        dec._state, logits, ids, top = step(
            dec._params, dec._state, staged(tokens, positions), pick)
        done, pending = pending, (t, live, logits, ids, top)
        if own:
            done, pending = pending, None
        if done:
            settle(*done)
    if pending:
        settle(*pending)
    return {
        "streams": {s: (list(streams[s][0]) + fed[s][:want[s] - 1],
                        len(streams[s][0]) + np.arange(want[s] - 1),
                        np.stack(rows[s]), np.stack(experts[s], axis=1))
                    for s in picked},
        "same": {s: (same[s], want[s]) for s in want},
        "states": slot_states(engine, picked)}


def compare(engine, ctx, replayed, states, pad_to: int, pad_rows: int,
            spoil=None) -> dict:
    """A replayed stream against the reference's full forward over its
    tokens, padded to `pad_to` (causal: the tail is unseen; one length and
    `pad_rows` compared rows, one set of programs): the largest logit
    error of its decoded rows, the routings that lie beyond the margin,
    and its slot's last state (`states`: the layers', from the engine's
    leaves) against the reference's after the last token fed."""
    tokens, at, rows, experts = replayed
    padded = np.zeros((pad_to,), np.int32)
    padded[:len(tokens)] = tokens
    program = np.full((experts.shape[0], pad_to, experts.shape[-1]), -1,
                      np.int32)
    program[:, at] = experts
    rows_at = np.zeros((max(pad_rows, len(at)),), np.int32)
    rows_at[:len(at)] = at
    want, report = reference.forward(
        harness.param_getter(engine.decode_model), padded, ctx.config,
        rows=rows_at, program_experts=program, route_margin=ROUTE_MARGIN,
        spoil=spoil, state_at=len(tokens) - 1)
    ends = report.pop("states")
    return {"error": reference.logit_error(rows, want[:len(at)]),
            "rows": len(at),
            "state_error": max(reference.logit_error(mine, theirs)
                               for mine, theirs in zip(states, ends)),
            "state_f32": min(map(f32_share, states)),
            **report}


def sound(r: dict) -> bool:
    return bool(r["error"] <= LOGIT_TOL
                and r["route_bad"] <= ROUTE_BAD_SHARE * r["routings"]
                and r["state_error"] <= STATE_END_TOL
                and r["state_f32"] >= STATE_F32_SHARE)


def said(r: dict) -> str:
    return (f"{r['error']:.5f} of max |logit| over {r['rows']} rows "
            f"(tolerance {LOGIT_TOL}); of {r['routings']} routings "
            f"{r['ties']} took experts of the program's that are not the "
            f"reference's and {r['route_bad']} lie beyond {ROUTE_MARGIN} "
            f"(largest shortfall {r['worst_shortfall']:.4f}); the slot's "
            f"last state {r['state_error']:.5f} of its largest entry "
            f"(tolerance {STATE_END_TOL}), {100 * r['state_f32']:.2f} % of "
            f"it no bfloat16")


def scoped_instructions(engine) -> dict:
    """The `[instruction name, scope]` pairs of the engine's pure-decode
    step for the per-layer readers of a traced run: the step lowered at
    the shapes the loop calls it with and compiled once more (the
    persistent cache has it)."""
    import jax
    import jax.numpy as jnp

    from benchmarks import dsv32_events, solar2_events

    dec, slots = engine.decode_model, engine.spec.slots
    xs = engine._stage_inputs(
        np.zeros((slots, 1), np.int32),
        np.full((slots, 1), engine.max_seq_len, np.int32))
    text = engine._step_fn.lower(
        dec._params, dec._state, xs, jnp.zeros((slots,), jnp.int32),
        jax.random.key(0), jnp.zeros((slots,), jnp.float32)
    ).compile().as_text()
    return {"decode_instructions": dsv32_events.scoped_instructions(text),
            "solar2_instructions": solar2_events.scoped_instructions(text)}


def pick_streams(pool, finished, slot_of) -> tuple:
    """({slot: the request replayed there}, the compared slots, how many
    of `pool` ran in a slot another request had left): a slot's stream is
    the last of `pool` it served, else the last it finished at all; the
    compared ones are the longest prompt of `pool`, the shortest prompt
    among those in a reused slot (what the slot's last request left
    weighs most on it), and the longest context."""
    first_in_slot, by_slot = {}, {}
    for r in sorted(finished, key=lambda r: r.request_id):
        first_in_slot.setdefault(slot_of[r.request_id], r.request_id)
    for r in [*finished, *pool]:
        by_slot[slot_of[r.request_id]] = r
    reused = [r for r in pool
              if first_in_slot[slot_of[r.request_id]] != r.request_id]
    picks = {}
    for r in (max(pool, key=lambda r: len(r.prompt)),
              *(min(reused, key=lambda r: len(r.prompt)),) * bool(reused),
              max(pool, key=lambda r: len(r.prompt) + len(r.generated))):
        picks.setdefault(slot_of[r.request_id], r)
    by_slot.update(picks)
    return by_slot, list(picks)[:CHECK_STREAMS], len(reused)


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
    print(f"[reason] engine: {slots} slots x {engine.max_seq_len}, prefill "
          f"chunk {engine.spec.prefill_chunk}, pool {mgr.num_blocks} blocks "
          f"of {mgr.block_size}, chunks as "
          f"{'rows' if engine._chunk_rows else 'a rectangle'}; "
          f"{engine.stats()['state_bytes'] / 1e9:.2f} GB of slot state")

    def padded(n: int) -> int:  # the lengths the reference compiles for
        return n + -n % 256

    rng = np.random.default_rng(ctx.seed)
    step = logits_step(engine, CHECK_STREAMS)
    sizes = traffic_gen.request_sizes(t)
    # every compared sequence is padded to one length and one count of
    # rows, so that the reference's programs are compiled once
    pad_to, pad_rows = padded(max(sizes[0]) + max(sizes[1])), max(sizes[1])
    chunk = engine.spec.prefill_chunk
    ahead = sessions.Ahead()
    # the buckets of the check's chunks, in order
    first = [engine._bucket(min(chunk, n - at))
             for n in t["check_prompt_tokens"] for at in range(0, n, chunk)]
    if engine._chunk_rows:
        with ctx.span("lower_ahead"):
            # every program of the run, lowered at the shapes the loop
            # calls it with (`lower_step`: a chunk as rows) and compiled
            # in threads beside the check, in the order of need: the
            # check's chunk steps and its logits step, the step that only
            # decodes, the mix's other chunks
            rest = {engine._bucket(n % chunk or chunk) for n in sizes[0]}
            for b in dict.fromkeys([*first, *sorted(rest, reverse=True)]):
                ahead.add(f"engine@{b}", sessions.lower_step(
                    engine, engine._step_fn, b, False))
                if b == first[-1]:
                    ahead.add("check@0", lower_logits_step(engine, step))
                    ahead.add("engine@0", sessions.lower_step(
                        engine, engine._step_fn, 0, False))
    with ctx.span("reference_check"):
        state_error = state_check(ctx, engine)
        print(f"[reason] the state update alone, {slots} rows of "
              f"{STATE_TOKENS} tokens a call at a time against the "
              f"reference's scan: {state_error:.2e} of the largest "
              f"(tolerance {STATE_TOL})")
        if ahead.pending:
            ahead.wait("check@0", *(f"engine@{b}" for b in first))
        prompts = {i: (rng.integers(0, vocab, n).tolist(), None)
                   for i, n in enumerate(t["check_prompt_tokens"])}
        got = replay(engine, step, prompts, list(prompts))
        check = [compare(engine, ctx, got["streams"][i], got["states"][i],
                         pad_to, pad_rows) for i in prompts]
    for r in check:
        print(f"[reason] decode-graph logits, chunked prefill + "
              f"{CHECK_DECODED} decoded: {said(r)}")

    scoped = {}
    if ctx.trace_dir:
        with ctx.span("scoped_instructions"):
            scoped = scoped_instructions(engine)

    stream = traffic_gen.requests(t, vocab, ctx.seed)
    asked, client_of, slot_of, steps, finished = {}, {}, {}, [], []

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
        for s in engine.scheduler.active_slots:
            slot_of.setdefault(s.request.request_id, s.index)
        for req in done:
            finished.append(req)
            submit(client_of[req.request_id])
        return done

    with ctx.span("compile_wait"):
        ahead.wait()  # nothing compiles beside the window
        ahead.pool.shutdown()
    with ctx.span("first_round"):
        # until as many requests have ended as there are clients (four
        # cycles of the mix's sizes: every prefill shape has run, and the
        # step that only decodes). Waiting for every client's first reply
        # instead is 2,048 steps and more, and a cold run then outlasts
        # the driver's limit (PERF.md section 6, PR 33)
        for c in range(t["clients"]):
            submit(c)
        shapes = {engine._bucket(n % chunk or chunk) for n in sizes[0]}
        while len(finished) < t["clients"] or shapes - {
                engine._bucket(len(r.prompt) % chunk or chunk)
                for r in finished}:
            pump()

    before = engine.stats()
    w0 = ctx.open_window()
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
    step_ms = sorted(1e3 * (b - a) for a, b, _ in steps
                     if a >= w0 and b <= w1) or [0.0]
    # the state the timed program left, from the engine's own leaves
    window_f32 = min(f32_share(layer) for layers in slot_states(
        engine, range(min(slots, CHECK_STREAMS))).values()
        for layer in layers)
    print(f"[reason] {len(ended)} requests ended in {ctx.window_s:.2f} s, "
          f"{tokens} tokens; an engine step: median "
          f"{step_ms[len(step_ms) // 2]:.2f} ms, 90th percentile "
          f"{step_ms[len(step_ms) * 9 // 10]:.2f}; "
          f"{grew('state_resets')} slots reset for a new request; "
          f"{100 * window_f32:.2f} % of the slots' state is no bfloat16")

    with ctx.span("stream_replay"):
        # the window's batch (of all that ended, where a short, traced
        # window saw none end): a stream a slot, every slot live
        pool = right or [r for r in finished if whole(r)]
        by_slot, picked, reused = pick_streams(
            pool, [r for r in finished if whole(r)], slot_of)
        got = replay(engine, step,
                     {s: (r.prompt, r.generated) for s, r in by_slot.items()},
                     picked)
    with ctx.span("stream_check"):
        results = []
        for s in picked:
            r = by_slot[s]
            results.append(compare(engine, ctx, got["streams"][s],
                                   got["states"][s], pad_to, pad_rows))
            print(f"[reason] served stream of {len(r.prompt)} + "
                  f"{len(r.generated)} tokens in slot {s}, replayed beside "
                  f"{len(got['same']) - 1} others ({got['same'][s][0]} of "
                  f"its tokens are the replay's own argmax): "
                  f"{said(results[-1])}")
    shares = {s: same / n for s, (same, n) in got["same"].items()}
    same_share = (sum(same for same, _ in got["same"].values())
                  / sum(n for _, n in got["same"].values()))
    apart = [s for s, share in shares.items() if share < SAME_SHARE]
    print(f"[reason] {len(shares)} served streams replayed, every slot "
          f"live: {100 * same_share:.2f} % of their "
          f"{sum(n for _, n in got['same'].values())} tokens are the "
          f"replay's argmax, {100 * min(shares.values()):.2f} % of the "
          f"stream that agrees least (the least allowed: "
          f"{100 * SAME_SHARE:.0f} %)")
    off = [r for r in results if not sound(r)]
    ttft, tpot = serve.request_latencies(right)
    in_window = [(a, b, pre) for a, b, pre in steps if a >= w0 and b <= w1]
    prefill_step_s = [b - a for a, b, pre in in_window if pre]
    print(f"[reason] {len(ended)} requests ended in {ctx.window_s:.2f} s "
          f"({len(wrong)} wrong), {tokens} tokens, "
          f"{len(in_window)} engine steps, {len(prefill_step_s)} of them "
          f"with a prefill chunk; {grew('moe_assignments')} expert "
          f"assignments computed, {grew('moe_dropped')} dropped; "
          f"{reused} of the streams that ended ran in a reused slot")
    latencies = serve.latency_statistics(ttft, tpot)
    phases = ("ffcompile", "lower_ahead", "reference_check", "compile_wait",
              "first_round", "stream_replay", "stream_check")
    print("[reason] seconds beside the window: " + ", ".join(
        f"{name} {sum(ctx.seconds_in(name)):.1f}" for name in phases))
    return {
        "attempted": len(ended),
        "failed": len(wrong) + len(off) + len(apart),
        "correct": bool(
            state_error <= STATE_TOL and all(map(sound, check))
            and window_f32 >= STATE_F32_SHARE
            and results and not off and not apart and not wrong
            and ended and grew("moe_dropped") == 0),
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
            "state_f32_share": min(
                [window_f32, *(r["state_f32"] for r in results)]),
            "replayed_streams": len(shares),
            "same_share": same_share,
            "same_share_min": min(shares.values()),
            "prefill_share_pct": 100.0 * sum(prefill_step_s) / ctx.window_s,
            "state_resets": grew("state_resets"),
            "moe_assignments": grew("moe_assignments"),
            **scoped,
            **{k: round(v, 3) for k, v in latencies.items()
               if v is not None},
        },
    }
