"""The session-serving job of `mimo2f-serve-longdoc`: the loop, the checks
and the replay of jobs/serve_sessions.py (sessions of one long history
each, held in the prefix cache; a request is history + a fresh question;
logits of the pre-window check and of two served streams against the
reference's full forward), over MiMo-V2-Flash and its reference
(benchmarks/mimo_v2_flash_reference.py).

serve_sessions.py names DeepSeek-V3.2's configuration builder and
reference and is an accepted file, so this job loads a copy of that module
of its own (`harness.load_module` executes the file anew), as
jobs/serve_mediaqa.py does, and gives the copy this configuration's parts:
`build_model`, `reference` (the same `compare` / `lowerings` / limits
interface; no layer selects, so the selection's readings are empty),
`Choices` (the experts a decoded row chose; no `sel_rows`),
`decode_instructions` (the scopes of benchmarks/mimo2_events.py beside
those dsv32_events.py joins), `logit_check` (the cache has two groups: the
check's hand-made page tables are two, one over each group's free blocks),
and the two additions below, which sit on `replay` and `compare`.
Everything else is that file's, line for line: the traffic, the window,
what `correct` needs of the logits, of the histories and of the experts.

**The pre-window check** prefills a prompt of 2,300 tokens in the cell's
chunks and decodes through tables that hold every block of both groups:
eighteen windows deep, it holds the kernels' band, start page, sink and
head sizes against the reference. What the block manager does to the
window group (blocks freed as a slot advances, a history matched where the
window group holds its last rows, the shared tail block copied in both
groups) is held by the replayed streams, which go through the engine's own
submit(), admission and steps.

**The last global layer's rows of a question** (`pool_rows`, saved after
the replay while the pools are still there, compared inside `compare`,
which runs the reference's forward anyway): layer 5's keys and values of a
compared stream's question positions, from the global pool (which keeps
them: the request's prompt is published), against the reference's. They
are a function of four window layers' outputs over the history's last 128
rows, so a window or a value scale computed wrongly when the question was
prefilled shows there at full size, in rows no decoded row's logits are
compared at. `correct` needs both streams' within `reference.CACHE_TOL`.

`run(ctx, control=...)` is for the builder's controls, which have to come
out not correct (PERF.md section 6, PR 41): a `spoil` of the reference
(mimo_v2_flash_reference.SPOILS), or "lost_window_block", which zeroes, in
every window layer, the window blocks every replayed stream's admission
will map (the last 127 rows of what the cache holds of its prompt) before
the replay.
"""

from __future__ import annotations

import sys
import types

import numpy as np

from benchmarks import harness
from benchmarks import mimo_v2_flash_reference as reference


def build_model(ctx):
    """The compiled model, from the flags a user would put on the command
    line: the trunk builder, an inference compile."""
    from flexflow_tpu import (
        FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
    )
    from flexflow_tpu.fftype import CompMode
    from flexflow_tpu.models import (
        build_transformer_lm, mimo_v2_flash_lm_config,
    )

    cell = ctx.cell
    cfg = mimo_v2_flash_lm_config(
        ctx.config, sequence_length=cell["train_sequence_length"],
        attention_impl=cell["attention_impl"],
        initializer_range=ctx.config["initializer_range"],
        embedding_range=ctx.config["embedding_initializer_range"],
        sink_range=ctx.config["sink_initializer_range"])
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


def logit_check(sessions, engine, ctx, prompts, step, pad_to=None) -> dict:
    """serve_sessions.logit_check over a cache of two groups: each prompt
    prefilled in the engine's own chunks (the chunk's tokens as rows past
    the slots, under one page-table row a group, through the engine's own
    step program), one prompt a slot, then CHECK_DECODED tokens decoded
    greedily, all prompts in one step (`step`, for the logits), and the
    logits those steps gave compared with the reference's full forward.
    The prompts lie in blocks both pools have free, every block of the
    window group's too: nothing is freed here (module docstring)."""
    import jax
    import jax.numpy as jnp

    dec = engine.decode_model
    slots, dead = engine.spec.slots, engine.max_seq_len
    chunk = engine.spec.prefill_chunk
    mgr = engine.block_manager
    n = len(prompts)
    need = -(-(max(map(len, prompts)) + sessions.CHECK_DECODED + 1)
             // mgr.block_size)
    tables = {}
    for name, free in (("page_table", mgr._free),
                       ("page_table_w", mgr.window._free)):
        if len(free) < n * need:
            raise ValueError(f"{name}: pool too small for the check prompts")
        table = np.zeros((slots, mgr.table_width), np.int32)
        table[:n, :need] = np.asarray(free[:n * need],
                                      np.int32).reshape(n, need)
        tables[name] = table
    choices = sessions.Choices(ctx)

    def staged(tokens, positions, row_slots):
        xs = engine._stage_inputs(tokens, positions, row_slots)
        for name, table in tables.items():
            xs[name] = jax.device_put(table[row_slots], xs[name].sharding)
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
            dec._state, sampled = engine._step_fn(
                dec._params, dec._state,
                staged(tokens, positions,
                       np.r_[np.arange(slots), np.full((chunk,), i)]),
                jnp.zeros((slots + chunk,), jnp.int32), jax.random.key(0),
                jnp.asarray(np.zeros((slots + chunk,), np.float32)))
        last.append(int(sampled[slots + len(part) - 1]))
    seqs = [[*p, first] for p, first in zip(prompts, last)]
    got = [{} for _ in prompts]
    program = [choices.empty() for _ in prompts]
    for _ in range(sessions.CHECK_DECODED):
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
        results.append(sessions.reference.compare(
            harness.param_getter(dec), s[:-1], ctx.config, rows, prog,
            pad_to=pad_to))
        print(f"[longdoc] prompt of {len(p)}: the decoded rows are "
              f"{results[-1]['error_by_row']} of max |logit| off the "
              f"reference")
    return sessions.merged(results)


def question_rows(engine, config, prompt, first: int) -> tuple:
    """(first, keys, values): what the global pool holds of `prompt`'s
    positions from `first` on in the last global layer, through the blocks
    the prefix cache maps for it (float32 numpy)."""
    import jax

    mgr = engine.block_manager
    layer = reference.last_global_layer(config)
    state = engine.decode_model._state[f"l{layer}_attn"]
    held, blocks = mgr.cache.match(prompt, peek=True)
    lb = first // mgr.block_size
    at = np.asarray(blocks[lb:], np.int32)
    lo = first - lb * mgr.block_size

    def rows(leaf):
        got = np.asarray(jax.device_get(state[leaf][at]), np.float32)
        return got.reshape(-1, got.shape[-1])[lo:held - lb * mgr.block_size]

    return first, rows("pool_k"), rows("pool_v")


def history_of(prompt, histories) -> list:
    """The session's history a request's prompt starts with."""
    return max((h for h in histories if prompt[:len(h)] == h), key=len)


def lose_window_block(engine, prompts) -> list:
    """Zero, in every window layer's pools, the window blocks each prompt's
    admission will map: those of the 127 rows before the length its cached
    extent is usable up to (the history's end, or further where the
    question's first block is cached still); -> the blocks lost."""
    from flexflow_tpu.serving.decode_graph import POOL_LEAVES

    mgr, dec = engine.block_manager, engine.decode_model
    lost = set()
    for p in prompts:
        covered, blocks = mgr._usable(p, *mgr.cache.match(p, peek=True))
        skip = min(covered, len(p) - 1)
        lost |= {mgr._wpins[blocks[lb]]
                 for lb in range(mgr.window.first_block(skip),
                                 (skip - 1) // mgr.block_size + 1)}
    lost = np.asarray(sorted(lost))
    for name in engine._window_nodes:
        leaves = dec._state[name]
        for leaf in POOL_LEAVES:
            if leaf in leaves:
                leaves[leaf] = leaves[leaf].at[lost].set(0)
    return lost.tolist()


def run(ctx, control=None) -> dict:
    sessions = harness.load_module("jobs", "serve_sessions.py")
    scoped, caches, saved, engines = {}, [], [], []

    class Choices(sessions.Choices):
        def __init__(self, ctx):
            layers = ctx.config["num_hidden_layers"]
            self.layers = range(layers)
            self.attn = []
            self.moe = {i: f"l{i}_moe" for i in self.layers
                        if ctx.config["moe_layer_freq"][i]}

        def empty(self) -> dict:
            return {layer: {"experts": {}} for layer in self.layers}

        def note(self, program, fetched, row: int, position: int) -> None:
            for layer, name in self.moe.items():
                program[layer]["experts"][position] = (
                    fetched[name]["expert_ids"][row])

    def decode_instructions(engine) -> list:
        """The pure-decode step's [[instruction, scope]] pairs under
        dsv32_events' scopes (returned: `decode_instructions`) and under
        mimo2_events' (`mimo2_instructions`), from one compiled text."""
        import jax
        import jax.numpy as jnp

        from benchmarks import dsv32_events, mimo2_events

        dec, slots = engine.decode_model, engine.spec.slots
        xs = engine._stage_inputs(
            np.zeros((slots, 1), np.int32),
            np.full((slots, 1), engine.max_seq_len, np.int32))
        text = engine._step_fn.lower(
            dec._params, dec._state, xs, jnp.zeros((slots,), jnp.int32),
            jax.random.key(0), jnp.zeros((slots,), jnp.float32)
        ).compile().as_text()
        scoped["mimo2_instructions"] = mimo2_events.scoped_instructions(text)
        return dsv32_events.scoped_instructions(text)

    if control not in (*reference.SPOILS, "lost_window_block"):
        raise ValueError(f"unknown control {control!r}")
    spoil = control if control in reference.SPOILS else None
    t = ctx.traffic
    lengths = sessions.traffic_gen.quantiles(t["history_tokens"],
                                             t["clients"])
    compared = sorted(lengths[c] for c in t["check_stream_histories"])
    # the sessions' histories, as the loop draws them (its first draws)
    rng = np.random.default_rng(ctx.seed)
    histories = [rng.integers(0, ctx.config["vocab_size"], n).tolist()
                 for n in lengths]

    def logits_step_and_keep(engine):
        # the first the job does with its engine: kept for what follows
        engines.append(engine)
        return logits_step(engine)

    def compare(get, tokens, config, rows, program, **kw):
        """reference.compare, with the pool's rows of the sequence's
        question where the replay saved them."""
        tokens = list(tokens)
        mine = next((s for s in saved if tokens[:len(s[0])] == s[0]), None)
        got = reference.compare(get, tokens, config, rows, program,
                                spoil=spoil,
                                pool_rows=mine[1] if mine else None, **kw)
        if mine:
            caches.append(got.pop("cache_error"))
            print(f"[longdoc] layer {reference.last_global_layer(config)}'s "
                  f"rows of a served question ({len(mine[1][1])} positions "
                  f"from {mine[1][0]}): {caches[-1]:.5f} of the largest off "
                  f"the reference's (tolerance {reference.CACHE_TOL})")
        return got

    replay, logits_step = sessions.replay, sessions.logits_step

    def replay_then_save_rows(engine, ctx, step, served):
        """The loop's replay, then the last global layer's rows of the
        compared sessions' questions, while the pools are still there (the
        loop deletes them after)."""
        by_history = {len(history_of(r.prompt, histories)): r
                      for r in served}
        if control == "lost_window_block":
            lost = lose_window_block(engine, [r.prompt for r in served])
            print(f"[longdoc] control: window blocks {lost} zeroed in "
                  f"every window layer before the replay")
        record = replay(engine, ctx, step, served)
        for n in compared:
            r = by_history.get(n)
            if r is not None:
                saved.append((list(r.prompt),
                              question_rows(engine, ctx.config, r.prompt, n)))
        return record

    def logit_check_two_groups(engine, ctx, prompts, step, pad_to=None):
        return logit_check(sessions, engine, ctx, prompts, step,
                           pad_to=pad_to)

    def warm_copies(engine):
        """serve_sessions.warm_copies in both groups: the pool's
        copy-on-write programs at every width they can take."""
        from flexflow_tpu.serving.paged import SCRATCH_BLOCK, CopyPlan

        width = 1
        while width <= engine.spec.slots:
            engine._apply_copies(
                [CopyPlan(src=SCRATCH_BLOCK, dst=SCRATCH_BLOCK, group=g)
                 for g in (0, 1)] * width)
            width *= 2

    sessions.build_model = build_model
    sessions.Choices = Choices
    sessions.decode_instructions = decode_instructions
    sessions.logits_step = logits_step_and_keep
    sessions.logit_check = logit_check_two_groups
    sessions.warm_copies = warm_copies
    sessions.replay = replay_then_save_rows
    sessions.reference = types.SimpleNamespace(
        **{**vars(reference), "compare": compare})
    close_window = ctx.close_window
    at_close = {}

    def closed():
        now = close_window()
        at_close.update(engines[0].stats())
        return now

    ctx.close_window = closed
    result = sessions.run(ctx)
    held = (len(caches) == len(compared)
            and max(caches) <= reference.CACHE_TOL)
    if not held:
        print(f"[longdoc] the global pool's rows of the compared sessions' "
              f"questions are off the reference's, or not all held: "
              f"{caches}")
        result["correct"] = False
        result["failed"] += len(compared)
    window_keys = ("kv_pool_bytes", "kv_cached_tokens",
                   "kv_window_pool_bytes", "kv_window_pool_blocks",
                   "kv_window_blocks_held", "kv_window_blocks_in_use_peak",
                   "kv_blocks_held", "window_blocks_freed",
                   "window_cow_copies", "window_pins_dropped")
    result["counters"].update(
        scoped, cache_error=max(caches, default=None),
        kv_bytes_a_token=(at_close["kv_pool_bytes"]
                          / at_close["kv_cached_tokens"]
                          if at_close.get("kv_cached_tokens") else None),
        **{k: at_close.get(k) for k in window_keys})
    return result
