"""The session-serving job of `evabyte-serve-bytedocs`: the loop, the checks
and the replay of jobs/serve_sessions.py (sessions of one history each,
held in the prefix cache; a request is history + a fresh turn; logits of
the pre-window check and of two served streams against the reference's
full forward), over EvaByte and its reference
(benchmarks/evabyte_reference.py): byte sessions of 4-27 k whose rows read
their aligned window of 2,048 exact keys and one summary for every 16
bytes of the windows closed before it.

serve_sessions.py names DeepSeek-V3.2's configuration builder and
reference and is an accepted file, so this job loads a copy of that module
of its own (`harness.load_module` executes the file anew), as
jobs/serve_agentmix.py does, and gives the copy this configuration's parts:
`build_model`, `reference` (the same `compare` / `lowerings` / limits
interface; no layer selects or routes, so those readings are empty),
`Choices` (nothing to record), `decode_instructions` (the scopes of
benchmarks/evabyte_events.py beside those dsv32_events.py joins). What a
cache of two groups asks of the pre-window check is serve_longdoc.py's
`logit_check` over two hand-made page tables, from a copy of that module.
Everything else is serve_sessions.py's, line for line: the traffic, the
window, what `correct` needs of the logits and of the histories. The
engine's report lacks the expert layers' counts (the model has none): the
loop reads them as 0.

**The pre-window check** prefills a prompt of 4,700 bytes in the cell's
chunks of 256 (two closed windows: 256 summaries; the decoded rows read 604
exact rows) and decodes 8 rows through tables that hold every block of
both groups, at a length of its own (the reference's forward over 4,864
bytes fits beside the pools; the streams' 21,760 would not): it holds the
kernels' two calls and their merge, the summaries a chunk writes and the
float32 residual stream against the reference. What the block manager does
(a closed window's blocks given back at the boundary, a history's pins, the
shared tail block copied in both groups) is held by the replayed streams,
which go through the engine's own submit(), admission and steps.

**The pools' rows of a prompt** (`pool_rows`, saved after the replay while
the pools are still there, compared inside `compare`, which runs the
reference's forward anyway): the last layer's exact keys and values of the
current window of a compared stream's prompt (history + turn), from the
window group through the blocks the prefix cache pins beside the prompt's
nodes, and its key and value summaries of every whole chunk of the prompt,
from the global group, against the reference's k, v, ksum, vsum. `correct`
needs both streams' within `reference.CACHE_TOL`.

**The stream's precision** (`reference.STREAM_SHARE`): over every compared
logit of the run (the check's and the two streams'), the share of what a
bfloat16 residual stream moves the reference's logits by that the
program's error carries (the error's projection on that move). A program
whose stream is float32 errs, by its bf16 matmuls, in a direction that
knows nothing of the move: 0 within a few thousandths. One whose stream is
rounded after every add rounds, early in the stack, where the reference
rounds, and carries a tenth of it.

`run(ctx, control=...)` is for the builder's controls, which have to come
out not correct (PERF.md section 6, PR 53): a `spoil` of the reference
(evabyte_reference.SPOILS), "lost_window_block" or "lost_summary_block"
(the streams replayed with the window group's, or the global group's,
blocks of their cached history zeroed), "bf16_stream" (the PROGRAM built
with `fp32_skip_add` false: under the cell's `--dtype bf16` its residual
stream is bfloat16, everything else as it is).
"""

from __future__ import annotations

import functools
import sys
import time
import types

import numpy as np

from benchmarks import evabyte_reference as reference
from benchmarks import harness

# a control that zeroes a cache group's blocks of the cached histories
LOST = {"lost_window_block": 1, "lost_summary_block": 0}
CONTROLS = (*LOST, "bf16_stream")


def build_model(ctx, stream32: bool = True):
    """The compiled model, from the flags a user would put on the command
    line: the trunk builder, an inference compile. `stream32` False: the
    control whose residual stream is of the compute dtype."""
    from flexflow_tpu import (
        FFConfig, FFModel, LossType, MetricsType, SGDOptimizer,
    )
    from flexflow_tpu.fftype import CompMode
    from flexflow_tpu.models import build_transformer_lm, evabyte_lm_config

    cell = ctx.cell
    cfg = evabyte_lm_config(
        ctx.config if stream32 else {**ctx.config, "fp32_skip_add": False},
        sequence_length=cell["train_sequence_length"],
        attention_impl=cell["attention_impl"])
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


def stream_line(readings) -> str:
    share, ratio = reference.stream_reading(readings)
    return (f"the logits' error carries {share:.4f} of what a bfloat16 "
            f"residual stream moves the reference's by, and is {ratio:.3f} "
            f"times its size")


def last_layer(config) -> str:
    return f"l{config['num_hidden_layers'] - 1}_attn"


def cached_blocks(engine, prompt) -> tuple:
    """(tokens of `prompt` the prefix cache holds, their global blocks,
    {logical block: the window block pinned beside it}): a peek."""
    mgr = engine.block_manager
    held, blocks = mgr.cache.match(prompt, peek=True)
    return held, blocks, {lb: mgr._wpins[b] for lb, b in enumerate(blocks)
                          if b in mgr._wpins}


def pool_rows(engine, config, prompt) -> tuple:
    """(first, k, v, ksum, vsum): what the pools hold of `prompt` in the
    last layer through the blocks the prefix cache maps for it (float32
    numpy): the exact rows of its current window, positions `first` to its
    cached end, and the summaries of its whole chunks from chunk 0."""
    import jax

    mgr = engine.block_manager
    bs, w = mgr.block_size, mgr.window
    state = engine.decode_model._state[last_layer(config)]
    held, blocks, pinned = cached_blocks(engine, prompt)
    first = w.first_row(held)
    lbs = list(range(first // bs, (held - 1) // bs + 1)) if held > first else []
    if any(lb not in pinned for lb in lbs):
        lbs = []  # a pin was given up: nothing to hold, which fails

    def rows(leaf, at, upto):
        got = np.asarray(jax.device_get(
            state[leaf][np.asarray(at, np.int32)]), np.float32)
        return got.reshape(-1, got.shape[-1])[:upto]

    exact = [rows(leaf, [pinned[lb] for lb in lbs],
                  held - first if lbs else 0)
             for leaf in ("pool_k", "pool_v")]
    chunks = held // config["chunk_size"]
    return (first, *exact,
            *(rows(leaf, blocks, chunks)
              for leaf in ("pool_ksum", "pool_vsum")))


def lose_blocks(engine, prompts, group: int) -> list:
    """Zero, in every layer's pools of one cache group, the blocks each
    prompt's admission will map from the prefix cache: the window group's
    (1: the exact rows of the cached extent's current window) or the global
    group's (0: every summary of the cached extent); -> the blocks lost."""
    mgr, dec = engine.block_manager, engine.decode_model
    lost = set()
    for p in prompts:
        covered, blocks = mgr._usable(p, *mgr.cache.match(p, peek=True))
        skip = min(covered, len(p) - 1)
        if group:
            lost |= {mgr._wpins[blocks[lb]]
                     for lb in range(mgr.window.first_block(skip),
                                     (skip - 1) // mgr.block_size + 1)}
        else:
            lost |= set(blocks[:mgr.window.first_block(skip)])
    lost = np.asarray(sorted(lost), np.int32)
    for name, s in engine._groups[group].items():
        leaves = dec._state[name]
        for leaf in s.names("block", group=group):
            leaves[leaf] = leaves[leaf].at[lost].set(0)
    return lost.tolist()


def run(ctx, control=None) -> dict:
    sessions = harness.load_module("jobs", "serve_sessions.py")
    # serve_longdoc.py's pre-window check over a cache of two groups
    two_groups = harness.load_module("jobs", "serve_longdoc.py")
    scoped, caches, saved, engines, streams = {}, [], [], [], []

    class Choices(sessions.Choices):
        """Nothing to record: no layer selects, none routes."""

        def __init__(self, ctx):
            self.layers = range(ctx.config["num_hidden_layers"])
            self.attn, self.moe = [], {}

        def empty(self) -> dict:
            return {}

        def note(self, program, fetched, row: int, position: int) -> None:
            pass

    def decode_instructions(engine) -> list:
        """The pure-decode step's [[instruction, scope]] pairs under
        dsv32_events' scopes (returned: `decode_instructions`; none match)
        and under evabyte_events' (`evabyte_instructions`), from one
        compiled text."""
        import jax
        import jax.numpy as jnp

        from benchmarks import dsv32_events, evabyte_events

        dec, slots = engine.decode_model, engine.spec.slots
        xs = engine._stage_inputs(
            np.zeros((slots, 1), np.int32),
            np.full((slots, 1), engine.max_seq_len, np.int32))
        text = engine._step_fn.lower(
            dec._params, dec._state, xs, jnp.zeros((slots,), jnp.int32),
            jax.random.key(0), jnp.zeros((slots,), jnp.float32)
        ).compile().as_text()
        scoped["evabyte_instructions"] = (
            evabyte_events.scoped_instructions(text))
        return dsv32_events.scoped_instructions(text)

    if control not in (*reference.SPOILS, *CONTROLS):
        raise ValueError(f"unknown control {control!r}")
    spoil = control if control in reference.SPOILS else None
    t = ctx.traffic
    lengths = sessions.traffic_gen.quantiles(t["history_tokens"],
                                             t["clients"])
    compared = sorted(lengths[c] for c in t["check_stream_histories"])
    check_length = reference.padded(
        max(t["check_history_tokens"]) + sessions.CHECK_DECODED + 1)
    # the sessions' histories, as the loop draws them (its first draws)
    rng = np.random.default_rng(ctx.seed)
    histories = [rng.integers(0, ctx.config["vocab_size"], n).tolist()
                 for n in lengths]

    def logits_step_and_keep(engine):
        # the first the job does with its engine: kept for what follows;
        # its report gains the expert layers' counts the loop reads
        engines.append(engine)
        stats = engine.stats
        engine.stats = lambda: {"moe_assignments": 0, "moe_dropped": 0,
                                **stats()}
        return logits_step(engine)

    def compare(get, tokens, config, rows, program, pad_to=None, **kw):
        """reference.compare, with the pools' rows of the sequence's prompt
        where the replay saved them; the pre-window check at a length of
        its own."""
        tokens = list(tokens)
        mine = next((s for s in saved if tokens[:len(s[0])] == s[0]), None)
        if len(tokens) <= check_length:
            pad_to = check_length
        got = reference.compare(get, tokens, config, rows, spoil=spoil,
                                pool_rows=mine[1] if mine else None,
                                pad_to=pad_to, **kw)
        # whether the program's residual stream is float32 (held to its
        # limit after the run, over all of its comparisons: the session
        # job's report has no place for it)
        stream = got.pop("stream")
        if stream is not None:
            streams.append(stream)
            print(f"[bytedocs] {len(tokens)} bytes, {len(rows)} compared "
                  f"rows: {stream_line([stream])}")
        if mine:
            caches.append(got.pop("cache_error"))
            first, k, _, ksum, _ = mine[1]
            print(f"[bytedocs] {last_layer(config)}'s rows of a served "
                  f"prompt of {len(mine[0])} bytes ({len(k)} exact rows "
                  f"from {first}, {len(ksum)} summaries): k, v, ksum, vsum "
                  f"{[round(e, 5) for e in got.pop('cache_errors')]} of the "
                  f"largest off the reference's (tolerance "
                  f"{reference.CACHE_TOL})")
        return got

    def lowerings(get, config, length, **kw):
        """The reference's programs at the streams' length and at the
        pre-window check's, with a float32 residual stream and with a
        bfloat16 one (`compare` runs both)."""
        return [program for n in (length, check_length)
                for spoil in (None, "bf16_residual")
                for program in reference.lowerings(get, config, n,
                                                   spoil=spoil, **kw)]

    replay, logits_step = sessions.replay, sessions.logits_step

    def replay_then_save_rows(engine, ctx, step, served):
        """The loop's replay, then the last layer's rows of the compared
        sessions' prompts, while the pools are still there (the loop
        deletes them after)."""
        by_history = {len(two_groups.history_of(r.prompt, histories)): r
                      for r in served}
        if control in LOST:
            lost = lose_blocks(engine, [r.prompt for r in served],
                               group=LOST[control])
            print(f"[bytedocs] control: blocks {lost} of the "
                  f"{'window' if LOST[control] else 'global'} group zeroed "
                  f"in every layer before the replay")
        record = replay(engine, ctx, step, served)
        for n in compared:
            r = by_history.get(n)
            if r is not None:
                saved.append((list(r.prompt),
                              pool_rows(engine, ctx.config, r.prompt)))
        crossed = sum(
            (len(r.prompt) + len(r.generated)) // ctx.config["window_size"]
            > len(r.prompt) // ctx.config["window_size"] for r in served)
        print(f"[bytedocs] {crossed} of the {len(served)} replayed streams "
              f"cross a window boundary while they decode")
        return record

    def logit_check_two_groups(engine, ctx, prompts, step, pad_to=None):
        return two_groups.logit_check(sessions, engine, ctx, prompts, step,
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

    sessions.build_model = functools.partial(
        build_model, stream32=control != "bf16_stream")
    sessions.Choices = Choices
    sessions.decode_instructions = decode_instructions
    sessions.logits_step = logits_step_and_keep
    sessions.logit_check = logit_check_two_groups
    sessions.warm_copies = warm_copies
    sessions.replay = replay_then_save_rows
    sessions.reference = types.SimpleNamespace(
        **{**vars(reference), "compare": compare, "lowerings": lowerings})
    close_window, open_window = ctx.close_window, ctx.open_window
    at_open, at_close, timed = {}, {}, []

    def closed():
        now = close_window()
        del engines[0].step  # the class's own again
        at_close.update(engines[0].stats())
        return now

    def opened():
        at_open.update(engines[0].stats())
        engine, step = engines[0], engines[0].step

        def timed_step():
            # the window's calls by kind on the host's clock: what a run
            # without a trace can say of where its window went
            before, t0 = engine._prefill_calls, time.perf_counter()
            done = step()
            timed.append((time.perf_counter() - t0,
                          engine._prefill_calls > before))
            return done

        engine.step = timed_step
        return open_window()

    ctx.open_window, ctx.close_window = opened, closed
    result = sessions.run(ctx)
    held = (len(caches) == len(compared)
            and max(caches) <= reference.CACHE_TOL)
    if not held:
        print(f"[bytedocs] the pools' rows of the compared sessions' "
              f"prompts are off the reference's, or not all held: {caches}")
        result["correct"] = False
        result["failed"] += len(compared)
    share = reference.stream_reading(streams)[0] if streams else None
    if streams:
        print(f"[bytedocs] over the run's {len(streams)} comparisons: "
              f"{stream_line(streams)} (limit {reference.STREAM_SHARE})")
        if share > reference.STREAM_SHARE:
            result["correct"] = False
            result["failed"] += 1
    window_keys = ("kv_pool_bytes", "kv_cached_tokens",
                   "kv_window_pool_bytes", "kv_window_pool_blocks",
                   "kv_window_blocks_held", "kv_window_blocks_in_use_peak",
                   "kv_blocks_held", "window_cow_copies",
                   "window_pins_dropped")
    grown = ("window_blocks_freed", "under_window", "eva_exact_rows",
             "eva_summary_rows", "eva_summaries_written", "eva_rollovers")
    result["counters"].update(
        scoped, cache_error=max(caches, default=None),
        stream_share=share,
        kv_bytes_a_token=(at_close["kv_pool_bytes"]
                          / at_close["kv_cached_tokens"]
                          if at_close.get("kv_cached_tokens") else None),
        # what the window's steps read, wrote and gave back
        **{k: at_close.get(k, 0) - at_open.get(k, 0) for k in grown},
        **{k: at_close.get(k) for k in window_keys})
    c = result["counters"]
    for kind, name in ((False, "that only decode"),
                       (True, "with a turn's chunk")):
        took = sorted(s for s, chunk in timed if chunk == kind) or [0.0]
        print(f"[bytedocs] the window's engine steps {name}, on the host's "
              f"clock: {len(took)} in {sum(took):.3f} s, mean "
              f"{1e3 * sum(took) / len(took):.3f} ms, median "
              f"{1e3 * took[len(took) // 2]:.3f}, 99th percentile "
              f"{1e3 * took[len(took) * 99 // 100]:.3f}, the longest "
              f"{1e3 * took[-1]:.3f}")
    steps = max(1, sum(not chunk for _, chunk in timed))
    print(f"[bytedocs] in the window, a layer: {c['eva_exact_rows']} exact "
          f"rows and {c['eva_summary_rows']} summaries attended by decoding "
          f"rows ({(c['eva_exact_rows'] + c['eva_summary_rows']) / steps:.0f}"
          f" a step), {c['eva_summaries_written']} summaries written, "
          f"{c['eva_rollovers']} slots opened a window, "
          f"{c['window_blocks_freed']} window blocks given back; the window "
          f"pool holds {at_close.get('kv_window_blocks_held')} of "
          f"{at_close.get('kv_window_pool_blocks')} blocks (peak in use "
          f"{at_close.get('kv_window_blocks_in_use_peak')}), "
          f"{at_close.get('window_pins_dropped')} pins dropped; the global "
          f"pool holds {at_close.get('kv_blocks_held')} blocks")
    return result
