"""The serving job: FFModel.compile -> serve() -> a closed loop of clients,
one per slot, each submitting its next request the moment its last one
finished (callers that wait for a reply: a batch pipeline, an agent loop).

Set-up: build and compile the model as a user does, build the engine,
compare the decode graph's logits (prefill through the paged cache, then
decoded tokens) with the reference's full forward, warm the decode step
and the pool's copy program, then run the loop until every client has one
request back and one whole cycle of the mix's sizes has been served: that
warms every prefill shape the mix can hit, and no other. Window: the same
loop, `engine.step()` after `engine.step()` in one thread, until the
window is up. After the window, streams the loop itself served inside it
(the longest prompt, which took the most chunks, among them) are held
against the reference's full forward over prompt and reply.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import harness, reference
from benchmarks import traffic as traffic_gen

CHECK_DECODED = 8
CHECK_STREAMS = 4
STATISTICS = {"mean": lambda v: float(np.mean(v)),
              "p50": lambda v: harness.percentile(v, 50),
              "p90": lambda v: harness.percentile(v, 90),
              "p95": lambda v: harness.percentile(v, 95)}


def request_latencies(finished):
    """(ttft seconds, seconds per output token after the first) of each
    finished request, from the scheduler's stamps."""
    ttft = [r.first_token_t - r.submit_t for r in finished]
    tpot = [(r.finish_t - r.first_token_t) / (len(r.generated) - 1)
            for r in finished if len(r.generated) > 1]
    return ttft, tpot


def came_back_right(req, asked: int, vocab_size: int) -> bool:
    return (req.finished and len(req.generated) == asked
            and all(0 <= t < vocab_size for t in req.generated))


def latency_statistics(ttft, tpot) -> dict:
    """`ttft_ms.<statistic>` and `tpot_ms.<statistic>` over all the
    requests given: the manifest names the ones that are metrics."""
    out = {}
    for name, seconds in (("ttft_ms", ttft), ("tpot_ms", tpot)):
        for stat, of in STATISTICS.items():
            out[f"{name}.{stat}"] = of(seconds) * 1e3 if seconds else None
    return out


def spread_by_prompt(reqs, n: int):
    """n of the requests, spread evenly over them by prompt length, the
    longest prompt among them."""
    ranked = sorted(reqs, key=lambda r: (len(r.prompt), r.request_id))
    if len(ranked) <= n:
        return ranked
    return [ranked[round(i * (len(ranked) - 1) / (n - 1))] for i in range(n)]


def off_the_reference(engine, ctx, reqs) -> list:
    """Those of the requests whose stream the reference would not have
    written: its full forward over prompt and reply, one stream at a time
    padded to the engine's longest sequence (one shape, one program)."""
    dec = engine.decode_model
    off = []
    for r in reqs:
        padded = np.zeros((1, engine.max_seq_len), np.int32)
        seq = [*r.prompt, *r.generated]
        padded[0, :len(seq)] = seq       # causal: the tail is unseen
        full = reference.forward_logits(
            harness.param_getter(dec), padded,
            num_layers=ctx.config["n_layer"], num_heads=ctx.config["n_head"])
        first = len(r.prompt) - 1
        if not reference.stream_agrees(
                full[0, first:first + len(r.generated)], r.generated):
            off.append(r)
    return off


def warm_copies(engine) -> None:
    """The pool's copy-on-write program at every width it can take (a
    power of two up to the slots). One copy a step is the rule here: the
    first reply token's write into a prompt's published tail block. Two
    come together when a prompt's first tokens are, by chance, those of a
    cached one, which the first round cannot be counted on to meet. The
    copies are scratch to scratch and move nothing."""
    from flexflow_tpu.serving.paged import SCRATCH_BLOCK, CopyPlan

    width = 1
    while width <= engine.spec.slots:
        engine._apply_copies(
            [CopyPlan(src=SCRATCH_BLOCK, dst=SCRATCH_BLOCK)] * width)
        width *= 2


def logit_error(engine, ctx, prompts) -> float:
    """The decode graph's logits against the reference's full forward:
    each prompt prefilled as one chunk through the paged cache, one prompt
    a slot, then CHECK_DECODED tokens decoded greedily through it. The
    step is the engine's own (`executor.build_decode_step`) but hands back
    the logits rows it samples from, and donates the cache like it."""
    import jax
    import jax.numpy as jnp

    dec, ex = engine.decode_model, engine.decode_model.executor
    slots, scratch = engine.spec.slots, engine.max_seq_len
    mgr = engine.block_manager
    # slot i reads and writes its own run of blocks (block 0 is scratch)
    table = (1 + np.arange(slots * mgr.table_width, dtype=np.int32)
             ).reshape(slots, mgr.table_width)
    if mgr.num_blocks <= slots * mgr.table_width:
        raise ValueError("pool too small to give every slot its own blocks")

    def step_logits(params, state, xs, read_idx):
        logits, new_state, _ = ex._apply(
            params, state, ex._cast_compute(xs), training=False, rng=None)
        rows = logits[jnp.arange(slots), read_idx].astype(jnp.float32)
        return ex._pin_at_rest(ex._restore_state_dtypes(new_state)), rows

    step = jax.jit(step_logits, donate_argnums=(1,))

    def call(tokens, positions, read_idx):
        xs = engine._stage_inputs(tokens, positions)
        xs["page_table"] = jax.device_put(table, xs["page_table"].sharding)
        dec._state, rows = step(dec._params, dec._state, xs,
                                jnp.asarray(read_idx, jnp.int32))
        return np.asarray(rows)

    n, width = len(prompts), max(len(p) for p in prompts)
    tokens = np.zeros((slots, width), np.int32)
    positions = np.full((slots, width), scratch, np.int32)
    read_idx = np.zeros((slots,), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
        positions[i, :len(p)] = np.arange(len(p))
        read_idx[i] = len(p) - 1
    rows = [call(tokens, positions, read_idx)[:n]]
    seqs = [list(p) for p in prompts]
    for _ in range(CHECK_DECODED):
        tokens = np.zeros((slots, 1), np.int32)
        positions = np.full((slots, 1), scratch, np.int32)
        for i, s in enumerate(seqs):
            s.append(int(np.argmax(rows[-1][i])))
            tokens[i, 0], positions[i, 0] = s[-1], len(s) - 1
        rows.append(call(tokens, positions, np.zeros((slots,), np.int32))[:n])
    program = np.stack(rows, axis=1)          # (n, 1 + decoded, vocab)

    longest = max(len(s) for s in seqs)
    padded = np.zeros((n, longest), np.int32)  # causal: the tail is unseen
    for i, s in enumerate(seqs):
        padded[i, :len(s)] = s
    full = reference.forward_logits(
        harness.param_getter(dec), padded,
        num_layers=ctx.config["n_layer"], num_heads=ctx.config["n_head"])
    ref = np.stack([full[i, len(p) - 1:len(p) + CHECK_DECODED]
                    for i, p in enumerate(prompts)])
    return reference.logit_error(program, ref)


def run(ctx) -> dict:
    t, cell = ctx.traffic, ctx.cell
    vocab = ctx.config["vocab_size"]
    cfg = harness.lm_config(ctx.config, ctx.config["n_positions"],
                            cell["attention_impl"])
    with ctx.span("ffcompile"):
        ff = harness.build_lm(
            cfg, [*cell["flags"], "--seed", str(ctx.seed % (2**31 - 1))],
            cell["train_batch"], cell["optimizer"])
    with ctx.span("ffcompile"):
        engine = ff.serve(**cell["serve"])
    mgr = engine.block_manager
    print(f"[serve] engine: {engine.spec.slots} slots x {engine.max_seq_len}"
          f", prefill chunk {engine.spec.prefill_chunk}, pool "
          f"{mgr.num_blocks} blocks of {mgr.block_size}")

    rng = np.random.default_rng(ctx.seed)
    with ctx.span("reference_check"):
        err = logit_error(engine, ctx, [
            rng.integers(0, vocab, n).tolist()
            for n in t["check_prompt_tokens"]])
    print(f"[serve] decode-graph logits against the reference, prefill + "
          f"{CHECK_DECODED} decoded: {err:.5f} of max |logit| (tolerance "
          f"{reference.LOGIT_TOL})")

    with ctx.span("warmup"):
        # the step that only decodes: where clients are few or prompts
        # long, the first round may never take one
        shortest = min(traffic_gen.request_sizes(t)[0])
        engine.generate([rng.integers(0, vocab, shortest).tolist()],
                        max_new_tokens=2)
        if mgr is not None:
            warm_copies(engine)

    stream = traffic_gen.requests(t, vocab, ctx.seed)
    asked, client_of, steps, finished = {}, {}, [], []
    rejected = []

    def submit(client: int):
        while True:
            prompt, new = next(stream)
            try:
                with ctx.span("submit"):
                    req = engine.submit(prompt, max_new_tokens=new)
            except ValueError:  # the engine refuses what it can never serve
                rejected.append(time.perf_counter())
                if len(rejected) > 100:
                    raise
                continue
            asked[req.request_id], client_of[req.request_id] = new, client
            return

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

    tokens_before = engine.stats()["decode_tokens"]
    w0 = ctx.open_window()
    while time.perf_counter() - w0 < ctx.seconds:
        pump()
    w1 = ctx.close_window()
    tokens = engine.stats()["decode_tokens"] - tokens_before

    ended = [r for r in finished if w0 <= r.finish_t <= w1]
    wrong = [r for r in ended
             if not came_back_right(r, asked[r.request_id], vocab)]
    right = [r for r in ended if r not in wrong]
    with ctx.span("stream_check"):
        checked = spread_by_prompt(right, CHECK_STREAMS)
        off = off_the_reference(engine, ctx, checked)
    refused = [r for r in rejected if w0 <= r <= w1]
    ttft, tpot = request_latencies(right)
    in_window = [(a, b, pre) for a, b, pre in steps if a >= w0 and b <= w1]
    prefill_step_s = [b - a for a, b, pre in in_window if pre]
    print(f"[serve] {len(ended)} requests ended in {ctx.window_s:.2f} s "
          f"({len(wrong)} wrong, {len(refused)} refused), {tokens} tokens, "
          f"{len(in_window)} engine steps, {len(prefill_step_s)} of them "
          f"with a prefill chunk")
    print(f"[serve] streams of the loop against the reference, prompts of "
          f"{[len(r.prompt) for r in checked]} tokens: {len(off)} of "
          f"{len(checked)} off it")
    latencies = latency_statistics(ttft, tpot)
    return {
        "attempted": len(ended) + len(refused),
        "failed": len(wrong) + len(refused) + len(off),
        "correct": bool(err <= reference.LOGIT_TOL and not wrong and not off
                        and not refused and ended),
        "end_to_end": {"serve_tok_s": tokens / ctx.window_s, **latencies},
        "counters": {
            "tokens": tokens, "requests": len(ended),
            "step_s": [b - a for a, b, _ in in_window],
            "prefill_step_s": prefill_step_s,
            "logit_error": err,
            "prefill_share_pct": 100.0 * sum(prefill_step_s) / ctx.window_s,
            **{k: round(v, 3) for k, v in latencies.items()
               if v is not None},
        },
    }
