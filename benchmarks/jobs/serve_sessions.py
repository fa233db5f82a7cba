"""The session-serving job: an inference compile of the configuration ->
serve() -> a closed loop of clients, one session each. A session owns a
fixed history; a request is `history + a fresh turn`, and the client sends
its session's next turn the moment the reply ends (people and agents asking
follow-up questions over one long document, repository or conversation).

Set-up: build and compile the model as a user does (the trunk builder,
FFModel.compile as an inference compile under the cell's flags), build the
engine, lower every program the run will call and hand them to threads that
compile them beside what follows (`Ahead`: a run has 360 s, and the step's
six programs and the reference's eighteen, compiled one after the other and
each when it was first called, took more than that), prefill every history
once through the engine so that its blocks lie in the prefix cache, warm the
pool's copy program, compare the decode graph's logits (a prompt prefilled
in the cell's own chunks through the paged latent cache, then decoded
tokens) with the reference's full forward, then run the loop until every
client has one request back and one whole cycle of the mix's sizes has been
served. Window: the same loop, `engine.step()` after `engine.step()` in one
thread. After the window, one stream the loop served from every session
goes through the engine once more, all 16 at once, for its logits
(`replay`), and those of the sessions the mix names
under `check_stream_histories` are held against the reference's full forward
over history, turn and reply: logits at the timed contexts, every slot live.

`correct` also needs: every request of the window found its whole history
in the prefix cache and every history still lies in the blocks set-up left
it in (the window evicts finished requests' tails, which nothing can match
again; a history evicted, or prefilled again, is another workload), and no
expert layer left an assignment to a held expert uncomputed.
"""

from __future__ import annotations

import concurrent.futures
import sys
import time

import numpy as np

from benchmarks import deepseek_v32_reference as reference
from benchmarks import harness
from benchmarks import traffic as traffic_gen

CHECK_DECODED = 8


def build_model(ctx):
    """The compiled model, from the flags a user would put on the command
    line: the trunk builder, an inference compile."""
    from flexflow_tpu import (
        FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
    )
    from flexflow_tpu.fftype import CompMode
    from flexflow_tpu.models import (
        build_transformer_lm, deepseek_v32_lm_config,
    )

    cell = ctx.cell
    cfg = deepseek_v32_lm_config(
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


def device_bytes_in_use() -> int:
    import jax

    return int((jax.devices()[0].memory_stats() or {}).get(
        "bytes_in_use", 0))


def logits_step(engine):
    """The engine's own graph as a step that hands back the logits rows
    where the engine's samples on the device: (state, (rows, vocab)
    float32). One jitted function for the check before the window and the
    replay after it (they call it at the same two shapes)."""
    import jax
    import jax.numpy as jnp

    ex = engine.decode_model.executor

    def step_logits(params, state, xs):
        logits, new_state, _ = ex._apply(
            params, state, ex._cast_compute(xs), training=False, rng=None)
        return (ex._pin_at_rest(ex._restore_state_dtypes(new_state)),
                logits[:, 0].astype(jnp.float32))

    return jax.jit(step_logits, donate_argnums=(1,))


class Ahead:
    """Programs compiled ahead of their first call, in threads (XLA compiles
    outside the interpreter's lock). `add` takes a program lowered in the
    caller's thread from one of jit's functions; a call of that function
    with arguments of the same shapes, types and placement then finds the
    lowering and its executable (jit keeps both) and neither traces nor
    compiles. `waited` is the seconds the caller has spent in `wait`."""

    def __init__(self):
        # three at a time, in the order they are added: one compile of XLA
        # takes most of a host's cores already, and eight beside each
        # other gave the first needed (a history's chunk step) after 63 s
        self.pool = concurrent.futures.ThreadPoolExecutor(3)
        self.pending, self.waited = {}, 0.0

    def add(self, name: str, lowered) -> None:
        # the executable stays with the lowering, where jit finds it
        self.pending[name] = self.pool.submit(
            lambda: lowered.compile() and None)

    def wait(self, *names: str) -> None:
        """Until the programs of these names, or all, are compiled."""
        t0 = time.perf_counter()
        for name in [n for n in self.pending if not names or n in names]:
            self.pending.pop(name).result()
        self.waited += time.perf_counter() - t0


def lower_step(engine, fn, bucket: int, logits: bool):
    """The engine's step (`fn` = engine._step_fn) or the logits step at
    the shape of a step that carries a chunk of `bucket` tokens as rows, 0
    for a step that only decodes: lowered from inputs staged as the engine
    stages them."""
    import jax
    import jax.numpy as jnp

    dec, slots = engine.decode_model, engine.spec.slots
    rows = slots + bucket
    xs = engine._stage_inputs(
        np.zeros((rows, 1), np.int32),
        np.full((rows, 1), engine.max_seq_len, np.int32),
        np.r_[np.arange(slots), np.zeros((bucket,), np.int64)]
        if bucket else None)
    if logits:
        return fn.lower(dec._params, dec._state, xs)
    return fn.lower(dec._params, dec._state, xs,
                    jnp.zeros((rows,), jnp.int32), jax.random.key(0),
                    jnp.asarray(np.zeros((rows,), np.float32)))


class Choices:
    """What the slots' rows chose in the last call, by layer: the
    positions each attended (`sel_rows`) and its experts (`expert_ids`).
    Selection and routing are discontinuous, so the reference needs them
    (benchmarks/deepseek_v32_reference.py `forward`)."""

    def __init__(self, ctx):
        layers = ctx.config["num_hidden_layers"]
        self.layers = range(layers)
        self.attn = [f"l{i}_attn" for i in self.layers]
        self.moe = {i: f"l{i}_moe" for i in self.layers
                    if i >= ctx.config["first_k_dense_replace"]}

    def empty(self) -> dict:
        return {layer: {"sel": {}, "experts": {}} for layer in self.layers}

    def fetch(self, state) -> dict:
        import jax

        return jax.device_get({
            name: {k: v for k, v in state[name].items()
                   if k in ("sel_rows", "expert_ids")}
            for name in (*self.attn, *self.moe.values())})

    def note(self, program, fetched, row: int, position: int) -> None:
        for layer in self.layers:
            mine = program[layer]
            mine["sel"][position] = fetched[self.attn[layer]]["sel_rows"][row]
            if layer in self.moe:
                mine["experts"][position] = (
                    fetched[self.moe[layer]]["expert_ids"][row])


def merged(results: list) -> dict:
    """The comparisons of several sequences as one: sums of the counts,
    the largest of the readings."""
    largest = ("error", "outside_max", "shortfall_max", "route_gap_max")
    return {k: (max if k in largest else sum)(r[k] for r in results)
            for k in results[0] if k != "error_by_row"}


def said(r: dict) -> str:
    return (f"{r['error']:.5f} of max |logit| off the reference (tolerance "
            f"{reference.LOGIT_TOL}); of {r['sel_rows']} selections held "
            f"against the reference's {r['sel_taken']} taken, "
            f"{r['sel_bad']} not allowed, at most {r['outside_max']} "
            f"positions outside (limit {reference.MAX_OUTSIDE}), shortfall "
            f"{r['shortfall_max']:.5f} (limit {reference.SEL_MARGIN}); "
            f"{r['route_taken']} of {r['route_rows']} routings taken at a "
            f"near-tie, the program's otherwise than the reference's at "
            f"{r['route_differs']}, at a gap of at most "
            f"{r['route_gap_max']:.5f} (limit {reference.ROUTE_MARGIN}), "
            f"{r['route_bad']} beyond it")


def logit_check(engine, ctx, prompts, step, pad_to=None) -> dict:
    """The decode graph's logits against the reference's full forward:
    each prompt prefilled in the engine's own chunks (the chunk's tokens
    as rows past the slots, under one page-table row, through the engine's
    own step program) through the paged latent cache, one prompt a slot,
    then CHECK_DECODED tokens decoded greedily through it, all prompts in
    one step (`step`, for the logits), and the logits those steps gave
    compared. The slots' choices at the decoded positions (the selected
    positions, the experts) go to the reference, which takes them at
    near-ties only. The prompts lie in blocks the pool has free: the
    histories are cached by now, and nothing is allocated meanwhile."""
    import jax
    import jax.numpy as jnp

    dec = engine.decode_model
    slots, dead = engine.spec.slots, engine.max_seq_len
    chunk = engine.spec.prefill_chunk
    mgr = engine.block_manager
    n = len(prompts)
    need = -(-(max(map(len, prompts)) + CHECK_DECODED + 1) // mgr.block_size)
    free = list(mgr._free)
    if len(free) < n * need:
        raise ValueError("pool too small for the check prompts")
    # slot i reads and writes its own run of free blocks (block 0, where
    # the table is not filled, is scratch)
    table = np.zeros((slots, mgr.table_width), np.int32)
    table[:n, :need] = np.asarray(free[:n * need], np.int32).reshape(n, need)
    choices = Choices(ctx)

    def staged(tokens, positions, row_slots):
        xs = engine._stage_inputs(tokens, positions, row_slots)
        xs["page_table"] = jax.device_put(table[row_slots],
                                          xs["page_table"].sharding)
        return xs

    last = []
    for i, p in enumerate(prompts):  # prefill, chunk by chunk
        for start in range(0, len(p), chunk):
            part = p[start:start + chunk]
            tokens = np.zeros((slots + chunk, 1), np.int32)
            positions = np.full((slots + chunk, 1), dead, np.int32)
            tokens[slots:slots + len(part), 0] = part
            positions[slots:slots + len(part), 0] = np.arange(
                start, start + len(part))
            # every row's argmax, as the engine samples a chunk's last
            # row (arguments as `_run_step` and `lower_step` make them)
            dec._state, sampled = engine._step_fn(
                dec._params, dec._state,
                staged(tokens, positions,
                       np.r_[np.arange(slots), np.full((chunk,), i)]),
                jnp.zeros((slots + chunk,), jnp.int32), jax.random.key(0),
                jnp.asarray(np.zeros((slots + chunk,), np.float32)))
        last.append(int(sampled[slots + len(part) - 1]))
    # a prompt's last row is sampled from and not compared: the program's
    # choices are known for the slots' rows only (`sel_rows`, `expert_ids`)
    seqs = [[*p, first] for p, first in zip(prompts, last)]
    got = [{} for _ in prompts]
    program = [choices.empty() for _ in prompts]
    for _ in range(CHECK_DECODED):
        tokens = np.zeros((slots, 1), np.int32)
        positions = np.full((slots, 1), dead, np.int32)
        for i, s in enumerate(seqs):
            tokens[i, 0], positions[i, 0] = s[-1], len(s) - 1
        dec._state, rows = step(dec._params, dec._state,
                                staged(tokens, positions, np.arange(slots)))
        rows = np.asarray(rows)
        fetched = choices.fetch(dec._state)
        for i, s in enumerate(seqs):
            got[i][len(s) - 1] = rows[i]
            choices.note(program[i], fetched, i, len(s) - 1)
            s.append(int(np.argmax(rows[i])))
    results = []
    for p, s, rows, prog in zip(prompts, seqs, got, program):
        results.append(reference.compare(
            harness.param_getter(dec), s[:-1], ctx.config, rows, prog,
            pad_to=pad_to))
        print(f"[sessions] prompt of {len(p)}: the decoded rows are "
              f"{results[-1]['error_by_row']} of max |logit| off the "
              f"reference")
    return merged(results)


def replay(engine, ctx, step, served) -> dict:
    """The logits of streams the loop served. The engine keeps tokens, not
    logits, so the requests `served` go through it once more, all at once
    (every slot live, as in the window): its own submit(), scheduler,
    block manager, radix match, copy-on-write and step layout, its own
    graph, with the device step of a step that only decodes swapped for
    `step`, which hands the logits rows back (a step that carries a turn's
    chunk runs the engine's own program and gives tokens: the streams that
    are compared are submitted last, so that all but one of their rows are
    decoded after the last chunk). Each slot is fed the token the loop
    served, whatever this pass would have sampled (the two programs part
    at bf16 near-ties), so the rows are the served stream's. ->
    {request_id: (rows {position: logits}, the program's choices there,
    positions whose argmax is the served token)} for the rows decoded in
    steps without a chunk; a stream's first token comes from a chunk
    step's row, whose choices the program does not record."""
    slots, choices = engine.spec.slots, Choices(ctx)
    record, again = {}, {}
    mine = engine._step_fn

    def recording(params, state, xs, read_idx, rng, temperature):
        first = [s for s in engine.scheduler.slots if s.prefilling]
        live = [s for s in engine.scheduler.slots if s.decoding]
        if first:
            new_state, out = mine(params, state, xs, read_idx, rng,
                                  temperature)
            out, head = np.array(out, np.int32), None
        else:
            new_state, rows = step(params, state, xs)
            head = np.asarray(rows[:slots])
            out = np.argmax(head, axis=-1).astype(np.int32)
            fetched = choices.fetch(new_state)
        for s in live:
            old = again[s.request.request_id]
            got, program, same = record[old.request_id]
            if head is not None:
                at = len(s.request.prompt) + len(s.request.generated) - 1
                got[at] = head[s.index]
                choices.note(program, fetched, s.index, at)
            want = old.generated[len(s.request.generated)]
            same.append(int(out[s.index]) == want)
            out[s.index] = want
        if first:  # the chunk's last live row samples the first token
            pre = min(first, key=lambda s: s.admit_seq)
            out[slots:] = again[pre.request.request_id].generated[0]
        return new_state, out

    engine._step_fn = recording
    try:
        for old in served:
            req = engine.submit(old.prompt,
                                max_new_tokens=len(old.generated))
            again[req.request_id] = old
            record[old.request_id] = ({}, choices.empty(), [])
        done = engine.run_until_drained()
    finally:
        engine._step_fn = mine
    for req in done:
        old = again[req.request_id]
        if req.generated != old.generated:
            raise RuntimeError("the replay was not fed the served stream")
    return record


def warm_copies(engine) -> None:
    """The pool's copy-on-write program at every width it can take (a
    power of two up to the slots): scratch to scratch, moving nothing.
    One copy a step is the rule (the first write into a history's shared
    tail block); two come together when two turns start in one step."""
    from flexflow_tpu.serving.paged import SCRATCH_BLOCK, CopyPlan

    width = 1
    while width <= engine.spec.slots:
        engine._apply_copies(
            [CopyPlan(src=SCRATCH_BLOCK, dst=SCRATCH_BLOCK)] * width)
        width *= 2


def decode_instructions(engine) -> list:
    """[[instruction name, scope]] of the engine's pure-decode step, for
    the per-layer readers of a traced run (benchmarks/dsv32_events.py):
    the step lowered at the shapes the loop calls it with and compiled
    once more (the persistent cache has it)."""
    import jax
    import jax.numpy as jnp

    from benchmarks import dsv32_events

    dec, slots = engine.decode_model, engine.spec.slots
    xs = engine._stage_inputs(
        np.zeros((slots, 1), np.int32),
        np.full((slots, 1), engine.max_seq_len, np.int32))
    text = engine._step_fn.lower(
        dec._params, dec._state, xs, jnp.zeros((slots,), jnp.int32),
        jax.random.key(0), jnp.zeros((slots,), jnp.float32)
    ).compile().as_text()
    return dsv32_events.scoped_instructions(text)


def history_blocks(engine, histories) -> list:
    """[(tokens of the history the prefix cache holds, their blocks)]: a
    peek, which touches neither the counters nor the cache's order."""
    return [engine.block_manager.cache.match(h, peek=True)
            for h in histories]


def run(ctx) -> dict:
    serve = harness.load_module("jobs", "serve.py")  # the latency arithmetic
    t, cell = ctx.traffic, ctx.cell
    vocab = ctx.config["vocab_size"]
    with ctx.span("ffcompile"):
        ff = build_model(ctx)
    with ctx.span("ffcompile"):
        engine = ff.serve(**cell["serve"])
    mgr = engine.block_manager
    print(f"[sessions] engine: {engine.spec.slots} slots x "
          f"{engine.max_seq_len}, prefill chunk {engine.spec.prefill_chunk}, "
          f"pool {mgr.num_blocks} blocks of {mgr.block_size}; "
          f"{device_bytes_in_use() / 1e9:.2f} GB on the device")

    rng = np.random.default_rng(ctx.seed)
    lengths = traffic_gen.quantiles(t["history_tokens"], t["clients"])
    histories = [rng.integers(0, vocab, n).tolist() for n in lengths]
    prompts = [rng.integers(0, vocab, n).tolist()
               for n in t["check_history_tokens"]]
    step = logits_step(engine)
    chunk, get = engine.spec.prefill_chunk, harness.param_getter(
        engine.decode_model)
    turns, replies = traffic_gen.request_sizes(t)

    def padded(n: int) -> int:  # the lengths the reference compiles for
        return n + -n % 256

    # every compared sequence is padded to one length, so that the
    # reference's nine programs are compiled once: the longest compared
    # history with the longest turn and the longest reply
    pad_to = padded(max(lengths[c] for c in t["check_stream_histories"])
                    + max(turns) + max(replies))
    ahead = Ahead()

    def last_bucket(n: int) -> int:  # of a history's last, shorter chunk
        return engine._bucket(n % chunk or chunk)

    with ctx.span("lower_ahead"):
        # in the order they are needed: the engine's steps (a history's
        # chunks, then its last, shorter one; a turn's; a step that only
        # decodes), then what the checks call: the logits step and the
        # reference's programs
        buckets = {engine._bucket(n) for n in turns} | {
            chunk, *map(last_bucket, lengths)}
        for bucket in sorted(buckets, reverse=True) + [0]:
            ahead.add(f"engine@{bucket}", lower_step(
                engine, engine._step_fn, bucket, False))
        ahead.add("check@0", lower_step(engine, step, 0, True))
        for name, lowered in reference.lowerings(
                get, ctx.config, pad_to, named=reference.ROWS):
            ahead.add("check@" + name, lowered)

    with ctx.span("prefill_histories"):
        t0 = time.perf_counter()
        # those whose every chunk is a whole one first: their program is
        # the first compiled, the others' follow beside the prefill
        for c in sorted(range(len(histories)),
                        key=lambda c: -last_bucket(lengths[c])):
            ahead.wait(f"engine@{chunk}",
                       f"engine@{last_bucket(lengths[c])}")
            engine.generate([histories[c]], max_new_tokens=1)
        ahead.wait("engine@0", *(f"engine@{b}" for b in buckets))
        # the step that only decodes: the first round may never take one
        engine.generate([rng.integers(0, vocab, min(turns)).tolist()],
                        max_new_tokens=2)
        warm_copies(engine)
    print(f"[sessions] {sum(lengths)} history tokens prefilled in "
          f"{time.perf_counter() - t0:.1f} s ({ahead.waited:.1f} of them "
          f"waiting for a step's program), {mgr.cached_blocks} blocks "
          f"cached; {device_bytes_in_use() / 1e9:.2f} GB on the device")
    with ctx.span("compile_wait"):
        ahead.wait()  # nothing compiles beside the window
        ahead.pool.shutdown()
    with ctx.span("reference_check"):
        check = logit_check(engine, ctx, prompts, step, pad_to=pad_to)
    print(f"[sessions] decode-graph logits, chunked prefill + "
          f"{CHECK_DECODED} decoded: {said(check)}")

    scoped = []
    if ctx.trace_dir:
        with ctx.span("scoped_instructions"):
            scoped = decode_instructions(engine)

    stream = traffic_gen.requests(t, vocab, ctx.seed)
    asked, client_of, steps, finished = {}, {}, [], []

    def submit(client: int):
        turn, new = next(stream)
        with ctx.span("submit"):
            req = engine.submit(histories[client] + turn,
                                max_new_tokens=new)
        asked[req.request_id], client_of[req.request_id] = new, client

    def pump():
        before = engine._prefill_calls
        t0 = time.perf_counter()
        with ctx.span("engine_step"):
            done = engine.step()
        steps.append((t0, time.perf_counter(),
                      engine._prefill_calls > before))
        for req in done:
            finished.append(req)
            submit(client_of[req.request_id])
        return done

    with ctx.span("first_round"):
        for c in range(t["clients"]):
            submit(c)
        waiting = set(range(t["clients"]))
        while waiting or len(finished) < t["cycle"]:
            waiting -= {client_of[r.request_id] for r in pump()}

    held = history_blocks(engine, histories)
    before = engine.stats()
    w0 = ctx.open_window()
    while time.perf_counter() - w0 < ctx.seconds:
        pump()
    w1 = ctx.close_window()
    after = engine.stats()
    tokens = after["decode_tokens"] - before["decode_tokens"]

    def grew(key):
        return after[key] - before[key]

    # the window may evict what no request can match again, the tails
    # (turn and reply) of finished requests; a history has to lie where
    # set-up put it
    moved = [c for c, (was, now) in enumerate(
        zip(held, history_blocks(engine, histories)))
        if was != now or was[0] < len(histories[c])]
    def whole(r):
        return (r.finished and len(r.generated) == asked[r.request_id]
                and all(0 <= tok < vocab for tok in r.generated))

    ended = [r for r in finished if w0 <= r.finish_t <= w1]
    wrong = [r for r in ended if not whole(r)]
    missed = [r for r in ended if r.matched_prefix_len
              < len(histories[client_of[r.request_id]])]
    right = [r for r in ended if r not in wrong]
    step_ms = sorted(1e3 * (b - a) for a, b, _ in steps
                     if a >= w0 and b <= w1) or [0.0]
    print(f"[sessions] {len(ended)} requests ended in {ctx.window_s:.2f} s, "
          f"{tokens} tokens, {device_bytes_in_use() / 1e9:.2f} GB on the "
          f"device; an engine step, which is every decoding slot's gap "
          f"between two tokens: median {step_ms[len(step_ms) // 2]:.2f} ms, "
          f"90th percentile {step_ms[len(step_ms) * 9 // 10]:.2f}")

    with ctx.span("drain"):
        engine.run_until_drained()  # what the window left in the slots
    with ctx.span("stream_replay"):
        served = []
        # one stream a session: 16 slots live; those compared go last
        for c in sorted(range(t["clients"]),
                        key=lambda c: c in t["check_stream_histories"]):
            mine = [r for r in finished
                    if client_of[r.request_id] == c and whole(r)]
            # of those that ended in the window (of all, where a short,
            # traced window saw none of this session end) the longest
            # reply whose turn the cache holds no more: the replay is to
            # prefill the turn as a chunk, as the loop did, and a prompt
            # found whole is a chunk of one token, another program
            ended_here = sorted([r for r in mine if r in right] or mine,
                                key=lambda r: -len(r.generated))
            served.append(next(
                (r for r in ended_here
                 if mgr.match_prefix(r.prompt) < len(r.prompt)),
                ended_here[0]))
        record = replay(engine, ctx, step, served)
    # the loop is over: its cache makes room for the reference's float32
    # pass over 16,000 tokens (the weights stay: the reference reads them)
    from flexflow_tpu.serving.decode_graph import POOL_LEAVES

    for leaves in engine.decode_model._state.values():
        for name in POOL_LEAVES:
            if name in leaves:
                leaves.pop(name).delete()
    with ctx.span("stream_check"):
        checked = [r for r in served
                   if client_of[r.request_id] in t["check_stream_histories"]]
        results = []
        for r in checked:
            rows, program, same = record[r.request_id]
            if not rows:  # a reply of two tokens, the second beside a chunk
                continue
            results.append(reference.compare(
                get, [*r.prompt, *r.generated[:-1]], ctx.config, rows,
                program, pad_to=pad_to))
            print(f"[sessions] served stream of {len(r.prompt)} + "
                  f"{len(r.generated)} tokens, replayed with "
                  f"{len(served)} slots live ({sum(same)} of {len(same)} "
                  f"tokens are the replay's own argmax): "
                  f"{said(results[-1])}")
    streams = merged(results) if results else None
    ttft, tpot = serve.request_latencies(right)
    in_window = [(a, b, pre) for a, b, pre in steps if a >= w0 and b <= w1]
    prefill_step_s = [b - a for a, b, pre in in_window if pre]
    hit = (100.0 * grew("prefix_hit_tokens") / grew("prompt_tokens")
           if grew("prompt_tokens") else 0.0)
    print(f"[sessions] {len(ended)} requests ended in {ctx.window_s:.2f} s "
          f"({len(wrong)} wrong, {len(missed)} without their whole history "
          f"from the cache), {tokens} tokens, {len(in_window)} engine steps, "
          f"{len(prefill_step_s)} of them with a turn's chunk; "
          f"{hit:.2f} % of {grew('prompt_tokens')} prompt tokens from the "
          f"cache, {grew('evictions')} cache nodes evicted (finished "
          f"requests' tails), {len(moved)} histories moved or evicted, "
          f"{grew('moe_assignments')} expert assignments computed, "
          f"{grew('moe_dropped')} dropped")

    def sound(r):
        return bool(r and r["error"] <= reference.LOGIT_TOL
                    and not r["sel_bad"] and not r["route_bad"])

    off = 0 if sound(streams) else len(checked)
    print(f"[sessions] streams of the loop against the reference, "
          f"{[len(r.prompt) for r in checked]} prompt tokens: "
          f"{'sound' if not off else 'off it'}")
    latencies = serve.latency_statistics(ttft, tpot)
    phases = ("ffcompile", "lower_ahead", "prefill_histories",
              "compile_wait", "reference_check", "first_round", "drain",
              "stream_replay", "stream_check")
    print("[sessions] seconds beside the window: " + ", ".join(
        f"{name} {sum(ctx.seconds_in(name)):.1f}" for name in phases))
    return {
        "attempted": len(ended),
        "failed": len(wrong) + len(missed) + off,
        "correct": bool(
            sound(check) and sound(streams)
            and not wrong and not missed and not moved and ended
            and len(results) == len(t["check_stream_histories"])
            and grew("moe_dropped") == 0),
        "end_to_end": {"serve_tok_s": tokens / ctx.window_s, **latencies},
        "counters": {
            "tokens": tokens, "requests": len(ended),
            "step_s": [b - a for a, b, _ in in_window],
            "prefill_step_s": prefill_step_s,
            "logit_error": check["error"],
            "stream_logit_error": streams["error"] if streams else None,
            "prefill_share_pct": 100.0 * sum(prefill_step_s) / ctx.window_s,
            "prefix_hit_pct": hit,
            "prompt_tokens": grew("prompt_tokens"),
            "evictions": grew("evictions"),
            "moe_assignments": grew("moe_assignments"),
            "decode_instructions": scoped,
            **{k: round(v, 3) for k, v in latencies.items()
               if v is not None},
        },
    }
